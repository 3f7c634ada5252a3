"""Share of the prefetch producer's loop that none of its stages covers:
100 x (1 - sum of ``znicz_pipeline_stage_seconds`` over fetch,
host_transform, h2d and enqueue / ``znicz_pipeline_producer_seconds``
sum), over the window.  ``crop_params`` and ``crop`` are parts of fetch
and ``h2d_landed`` runs beside the loop, so none of them is added."""

TILING_STAGES = ("fetch", "host_transform", "h2d", "enqueue")


def read(obs):
    loop = obs["registry"].hist("znicz_pipeline_producer_seconds")
    if loop is None or loop["sum"] <= 0:
        return None
    staged = sum(
        (obs["registry"].hist("znicz_pipeline_stage_seconds", stage=s)
         or {"sum": 0.0})["sum"]
        for s in TILING_STAGES
    )
    return 100.0 * (1.0 - staged / loop["sum"])
