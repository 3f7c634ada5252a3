"""Programs the engine compiled inside the window:
``znicz_serve_compiles_total`` after - before.  0 is expected."""


def read(obs):
    compiled = obs["registry"].value("znicz_serve_compiles_total")
    return 0.0 if compiled is None else compiled
