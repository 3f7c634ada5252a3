"""The share of the admitted prompts' tokens that the prefix cache held,
over the window: ``znicz_serve_prefix_cached_tokens_total`` /
``znicz_serve_prompt_tokens_total``, in percent.  With a 65,536-token prefix
ahead of an own turn of a few hundred tokens: about 99."""


def read(obs):
    cached = obs["registry"].value("znicz_serve_prefix_cached_tokens_total")
    prompts = obs["registry"].value("znicz_serve_prompt_tokens_total")
    if cached is None or not prompts:
        return None
    return 100.0 * cached / prompts
