"""The indexer's share of its roofline in a decode step of the traced
seconds, for a tower whose cached rows are ``[v, k]``: the cached keys a
layer's indexer scored
(``znicz_serve_sparse_keys_scored_total{phase=decode}``) x 64 values x 2 B
and 2 x 16 x 64 FLOPs a key (``harness/keye_work.index_scores``) against
the device time of the operations the program marks ``dsa_indexer`` inside
``jit__paged_decode_chunk``.  The keys lie in whole 128-lane tiles, half
zeros, so a fetch of every key reads twice these bytes."""

from harness import keye_readers as _shared, keye_work


def read(obs):
    return _shared.scope_roofline_pct(
        obs, "dsa_indexer",
        lambda means: keye_work.index_scores(means["cfg"], means["scored"]),
    )
