"""The grouped expert products' share of their roofline in a decode step:
the weights of the experts hit and the pairs' FLOPs (``harness/
axk1_work.experts_product``) against the device time of the operations
the program marks ``moe_experts`` inside ``jit__paged_decode_chunk``."""

from harness import axk1_readers as _shared, axk1_work


def read(obs):
    def work_of(means):
        cfg = means["cfg"]
        return (
            axk1_work.experts_product(
                cfg, means["experts_hit_per_layer"], means["pairs_per_layer"]
            ),
            axk1_work.routed_layers(cfg),
        )

    return _shared.scope_roofline_pct(obs, "moe_experts", work_of)
