"""Share of the collective time during which no other operation ran on the
chip, on the chip where the exposed time is longest."""


def read(obs):
    trace = obs["trace"]
    if trace is None or not trace.devices:
        return None
    pairs = list(zip(trace.collective_exposed_s(), trace.collective_s()))
    exposed, whole = max(pairs)
    return 100.0 * exposed / whole if whole > 0 else None
