"""Services: plotting, image saving, web status.

Replaces the reference's service stack [SURVEY.md 2.1 "Plotting service",
"Web status"; 2.3 "NN plotters", "Image saver"]: the reference publishes
pickled plotter state over ZMQ to a separate matplotlib process and serves a
tornado dashboard; here plotting renders headless PNGs/CSV in-process (no
remote display exists on a TPU pod host) and the status service writes a
JSON/HTML snapshot per epoch.
"""

from znicz_tpu.services.plotting import (  # noqa: F401
    AccumulatingPlotter,
    MetricsCSVWriter,
    Weights2D,
)
from znicz_tpu.services.engine import (  # noqa: F401
    Completion,
    PagedDecodeEngine,
)
from znicz_tpu.services.errors import (  # noqa: F401
    EngineClosedError,
    PrefixCacheUnsupportedError,
    RejectedError,
    RequestTooLargeError,
    SpeculationUnsupportedError,
    retryable,
)
from znicz_tpu.services.frontdoor import (  # noqa: F401
    RequestHandle,
    ServingFrontDoor,
)
from znicz_tpu.services.image_saver import ImageSaver  # noqa: F401
from znicz_tpu.services.publishing import MarkdownReporter  # noqa: F401
from znicz_tpu.services.web_status import StatusWriter  # noqa: F401
