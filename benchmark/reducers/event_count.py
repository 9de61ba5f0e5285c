"""How often the harness saw ``event`` inside the window (its own
listener on jax's monitoring events, counted after warm-up)."""


def reduce(reading, event: str):
    return reading.counters.get(event)
