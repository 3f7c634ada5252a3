"""Preemptions under pool pressure for each request first admitted in the
window: ``znicz_serve_preemptions_total`` /
``znicz_serve_requests_admitted_total``.  A preempted row is recomputed
from its prompt: every one costs a whole prefill."""


def read(obs):
    preempted = obs["registry"].value("znicz_serve_preemptions_total")
    admitted = obs["registry"].value("znicz_serve_requests_admitted_total")
    if preempted is None or not admitted:
        return None
    return preempted / admitted
