"""Reader ``summary_key``: one key of the engine's ``ServingMetrics.summary()``
taken over the window only (the driver swaps in a fresh ``ServingMetrics``
when the window opens and takes it out when it closes). ``scale`` turns a
share of 1 into percent."""


def read(obs, key, scale=1.0):
    value = obs.get("summary", {}).get(key)
    return None if value is None else float(value) * scale
