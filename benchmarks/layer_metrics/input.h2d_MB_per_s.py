"""Host-to-device rate while a transfer was being issued:
``znicz_h2d_bytes_total`` / ``znicz_pipeline_stage_seconds{stage=h2d}``
over the window.  The seconds are the host's ``device_put`` calls, which
return before the copy lands, so this is an upper reading of the link."""


def read(obs):
    sent = obs["registry"].value("znicz_h2d_bytes_total")
    spent = obs["registry"].hist("znicz_pipeline_stage_seconds", stage="h2d")
    if not sent or spent is None or spent["sum"] <= 0:
        return None
    return sent / spent["sum"] / 1e6
