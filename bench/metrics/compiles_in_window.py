"""Backend compiles and persistent-cache loads inside the timed window,
from jax's own monitoring events: 0 when set-up warmed every program."""


def read(rec):
    return rec["compiles"]
