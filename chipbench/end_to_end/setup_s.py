"""``setup_s``: process start to window open: imports, reaching the chip,
weights from the seed, building and compiling (or loading) every program,
warm-up and the lead-in traffic."""


def value(obs):
    return obs["setup_s"]
