"""The selecting layers' attention's share of its roofline in a decode step
of the traced seconds: the keys a full layer's queries kept
(``znicz_serve_sparse_keys_selected_total{phase=decode}``) x 1,152 B and
the absorbed products' FLOPs over them (``harness/dots3_work
.sparse_attention``: what the selection makes NECESSARY) against the device
time of the operations the program marks ``mla_sparse`` inside
``jit__paged_decode_chunk`` (the folds and the kernel that walks each live
row's blocks under its mask: it fetches every block, so the share says how
much of its time a fetch of the kept rows alone would take)."""

from harness import dots3_readers as _shared, dots3_work


def read(obs):
    def work_of(means):
        cfg = means["cfg"]
        return (
            dots3_work.sparse_attention(cfg, means["selected"]),
            dots3_work.layers_of(cfg)["global"],
        )

    return _shared.scope_roofline_pct(obs, "mla_sparse", work_of)
