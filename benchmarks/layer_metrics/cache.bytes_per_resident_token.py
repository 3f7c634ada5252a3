"""Pool bytes that rows' block tables reference for each token resident in
them, every kind of block, as the mean over the window's engine ticks
(``znicz_serve_cache_bytes_per_resident_token``, sum / count).  One table
a row would read 8 layers x 2,048 B = 16,384; with a table a kind the six
window layers give back what lies behind the window
(``harness/smallthinker_work.resident_token_bytes``)."""


def read(obs):
    hist = obs["registry"].hist("znicz_serve_cache_bytes_per_resident_token")
    if not hist:
        return None
    return hist["sum"] / hist["count"]
