"""Window-kind blocks a request took from the prefix cache when it was bound
to a slot, a request that mapped a cached chain at all:
``znicz_serve_prefix_blocks_mapped_total{kind=window}`` /
``znicz_serve_prefix_hit_requests_total``, over the window.  4 where the
window's 512 keys lie in whole blocks of 128 (the match's last window, which
is all a window layer reads of it); under 4 says some matches were cut
because the window kind no longer held its part
(``znicz_serve_prefix_chain_cut_total{reason=window_not_held}``)."""


def read(obs):
    window = obs["registry"].value(
        "znicz_serve_prefix_blocks_mapped_total", kind="window"
    )
    hits = obs["registry"].value("znicz_serve_prefix_hit_requests_total")
    if window is None or not hits:
        return None
    return window / hits
