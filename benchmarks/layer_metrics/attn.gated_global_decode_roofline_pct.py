"""The global layers' 48-head grouped-query attention's share of its
roofline in a decode step of the traced seconds: the cached rows a global
layer read (``znicz_serve_decode_cached_rows_total{kind=global}``) x 4,096 B
and 4 x 48 x 128 FLOPs a row (``harness/laguna_work.gqa_attention``) against
the device time of the operations the program marks ``attn_global`` inside
``jit__paged_decode_chunk``."""

from harness import laguna_readers as _shared


def read(obs):
    return _shared.attention_roofline_pct(obs, "global")
