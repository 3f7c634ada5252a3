"""The share of the keys its indexer scored that a full layer's attention
read, over the window's decode steps:
``znicz_serve_sparse_keys_selected_total{phase=decode}`` /
``znicz_serve_sparse_keys_scored_total{phase=decode}``, in percent.  At
2,048 keys kept of rows 3k-33k tokens long: what the selection spares the
attention."""


def read(obs):
    scored = obs["registry"].value(
        "znicz_serve_sparse_keys_scored_total", phase="decode"
    )
    selected = obs["registry"].value(
        "znicz_serve_sparse_keys_selected_total", phase="decode"
    )
    if not scored or selected is None:
        return None
    return 100.0 * selected / scored
