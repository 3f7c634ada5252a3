"""The kept keys' attention's share of its roofline in a decode step of the
traced seconds: the keys a layer's queries kept
(``znicz_serve_sparse_keys_selected_total{phase=decode}``) x 2,048 B and 2
x 2 x 32 x 128 FLOPs a key (``harness/keye_work.kept_attention``: what the
selection makes NECESSARY, whatever the kernel fetches) against the device
time of the operations the program marks ``gqa_sparse`` inside
``jit__paged_decode_chunk`` (the mask turned into the kept keys' slots, the
fetch of the rows they name, the two products)."""

from harness import keye_readers as _shared, keye_work


def read(obs):
    return _shared.scope_roofline_pct(
        obs, "gqa_sparse",
        lambda means: keye_work.kept_attention(means["cfg"], means["selected"]),
    )
