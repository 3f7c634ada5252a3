"""K/V positions the paged decode program gathers per token step, every
slot counted (slots x window blocks x block size, averaged over the
window's steps): ``znicz_serve_decode_gathered_tokens_total`` /
``znicz_serve_decode_steps_total``.  What ``decode.device_ms`` should
scale with once the per-call pool copies are gone."""


def read(obs):
    gathered = obs["registry"].value("znicz_serve_decode_gathered_tokens_total")
    steps = obs["registry"].value("znicz_serve_decode_steps_total")
    if gathered is None or not steps:
        return None
    return gathered / steps
