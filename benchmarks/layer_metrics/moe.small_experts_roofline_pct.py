"""The 256 small SiLU-gated experts' grouped products' share of their
roofline in a decode step of the traced seconds: 6.29 MB of weights an
expert hit (256 less the idle-experts counter a layer step) and the pairs'
FLOPs (``harness/laguna_work.experts_product``) against the device time of
the operations the program marks ``moe_experts`` inside
``jit__paged_decode_chunk``, over the routed layers."""

from harness import laguna_readers as _shared, laguna_work


def read(obs):
    def work_of(means):
        cfg = means["cfg"]
        return (
            laguna_work.experts_product(
                cfg, means["experts_hit_per_layer"], means["pairs_per_layer"]
            ),
            laguna_work.routed_layers(cfg),
        )

    return _shared.scope_roofline_pct(obs, "moe_experts", work_of)
