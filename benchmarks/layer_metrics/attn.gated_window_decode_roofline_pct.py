"""The window layers' 64-head grouped-query attention's share of its
roofline in a decode step of the traced seconds: the cached rows a window
layer read (``znicz_serve_decode_cached_rows_total{kind=window}``) x 4,096 B
and 4 x 64 x 128 FLOPs a row (``harness/laguna_work.gqa_attention``) against
the device time of the operations the program marks ``attn_window`` inside
``jit__paged_decode_chunk`` (the kernel that reads the pool in place, the
turn of the ring before it and the pick of the value columns after it)."""

from harness import laguna_readers as _shared


def read(obs):
    return _shared.attention_roofline_pct(obs, "window")
