"""Host time per step inside ``repro.engine.dispatch`` (the engine's
step up to its host sync), in the traced window."""
from bench.scopes import per_step_ms


def read(rec):
    return per_step_ms(rec, "host_s", "repro.engine.dispatch")
