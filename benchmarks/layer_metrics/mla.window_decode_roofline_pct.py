"""The window layers' latent attention's share of its roofline in a decode
step of the traced seconds: the cached rows a window layer read
(``znicz_serve_decode_cached_rows_total{kind=window}``) x 2,176 B and the
absorbed products' FLOPs (``harness/dots3_work.window_attention``) against
the device time of the operations the program marks ``mla_window`` inside
``jit__paged_decode_chunk`` (the kernel that reads the pool in place, the
turn of the ring before it and the folds around it)."""

from harness import dots3_readers as _shared, dots3_work


def read(obs):
    def work_of(means):
        cfg = means["cfg"]
        return (
            dots3_work.window_attention(cfg, means["window"]),
            dots3_work.layers_of(cfg)["window"],
        )

    return _shared.scope_roofline_pct(obs, "mla_window", work_of)
