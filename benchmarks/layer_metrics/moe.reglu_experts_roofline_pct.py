"""The ReLU-gated grouped expert products' share of their roofline in a
decode step of the traced seconds: the weights of the experts hit and the pairs' FLOPs
(``harness/smallthinker_work.experts_product``) against the device time of
the operations the program marks ``moe_experts`` inside
``jit__paged_decode_chunk``."""

from harness import smallthinker_readers as _shared, smallthinker_work


def read(obs):
    def work_of(means):
        cfg = means["cfg"]
        return (
            smallthinker_work.experts_product(
                cfg, means["experts_hit_per_layer"], means["pairs_per_layer"]
            ),
            cfg["num_hidden_layers"],
        )

    return _shared.scope_roofline_pct(obs, "moe_experts", work_of)
