"""The indexer's share of its roofline in a decode step of the traced
seconds: the cached keys a full layer's indexer scored
(``znicz_serve_sparse_keys_scored_total{phase=decode}``) x 256 B and 2 x 64
x 128 FLOPs a key (``harness/dots3_work.index_scores``) against the device
time of the operations the program marks ``dsa_indexer`` inside
``jit__paged_decode_chunk`` (the fetch of the keys through the block table,
the products, the ReLU and the sum over heads)."""

from harness import dots3_readers as _shared, dots3_work


def read(obs):
    def work_of(means):
        cfg = means["cfg"]
        return (
            dots3_work.index_scores(cfg, means["scored"]),
            dots3_work.layers_of(cfg)["global"],
        )

    return _shared.scope_roofline_pct(obs, "dsa_indexer", work_of)
