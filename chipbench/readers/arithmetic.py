"""Reader ``arithmetic``: ``scale * numerator / denominator`` of two of the
run's facts (``obs["facts"]``: the window's summary keys as ``summary.<key>``,
the table of peaks as ``peak.<key>``, and what the driver counted). Nothing
to read, or a denominator of 0, gives nothing."""


def read(obs, numerator, denominator=None, scale=1.0):
    facts = obs["facts"]
    num = facts.get(numerator)
    den = 1.0 if denominator is None else facts.get(denominator)
    if num is None or not den:
        return None
    return scale * float(num) / float(den)
