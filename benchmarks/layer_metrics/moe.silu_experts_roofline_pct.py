"""The SiLU-gated grouped expert products' share of their roofline in a
decode step of the traced seconds, every expert held: the weights of the
experts hit and the pairs' FLOPs (``harness/keye_work.experts_product``)
against the device time of the operations the program marks ``moe_experts``
inside ``jit__paged_decode_chunk``."""

from harness import keye_readers as _shared, keye_work


def read(obs):
    return _shared.scope_roofline_pct(
        obs, "moe_experts",
        lambda means: keye_work.experts_product(
            means["cfg"], means["experts_hit"], means["pairs"]
        ),
    )
