"""Mean fenced decode launch of the engine in the window: the sum over the
count of ``engine.decode_step_ms`` (``launch/engine.py``)."""


def read(r):
    return r.mean_ms("engine.decode_step_ms")
