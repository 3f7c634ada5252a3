"""Device milliseconds a prefill chunk spends in its full layers' indexer,
selection and attention over the selected rows (both full layers
together): the operations the program marks ``dsa_indexer``, ``dsa_select``
and ``mla_sparse`` inside ``jit__paged_prefill_prog``, over its whole
executions in the trace, a chunk."""

from harness import dots3_readers as _shared


def read(obs):
    return _shared.prefill_scopes_ms_per_chunk(
        obs, ("dsa_indexer", "dsa_select", "mla_sparse")
    )
