"""The absorbed latent attention's share of its roofline in a decode
step: every gathered latent row read once and the folded products' FLOPs
(``harness/axk1_work.absorbed_attention``) against the device time of the
operations the program marks ``mla_absorbed`` inside
``jit__paged_decode_chunk`` (the gather of the window among them)."""

from harness import axk1_readers as _shared, axk1_work


def read(obs):
    def work_of(means):
        cfg = means["cfg"]
        return (
            axk1_work.absorbed_attention(
                cfg, cfg["serving"]["slots"], means["keys_per_row"]
            ),
            cfg["num_hidden_layers"],
        )

    return _shared.scope_roofline_pct(obs, "mla_absorbed", work_of)
