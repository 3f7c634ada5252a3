"""The global layers' grouped-query attention's share of its roofline in a
decode step of the traced seconds: the cached rows a global layer read
(``znicz_serve_decode_cached_rows_total{kind=global}``) x 2,048 B and the
products' FLOPs (``harness/smallthinker_work.gqa_attention``) against the
device time of the operations the program marks ``attn_global`` inside
``jit__paged_decode_chunk``."""

from harness import smallthinker_readers as _shared


def read(obs):
    return _shared.attention_roofline_pct(obs, "global")
