"""Backend compiles (cache hits included) that ended inside the window, as
``jax.monitoring`` reports them.  Every shape is warmed in set-up, so a
sound run reads 0."""


def read(r):
    return r.get("compiles_in_window")
