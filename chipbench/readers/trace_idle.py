"""Reader ``trace_idle``: the share of the traced slice in which no
operation ran on the device: 1 - (union of device-op intervals) / slice,
averaged over the chips used."""


def read(obs):
    tr = obs.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
