"""``train_samples_s``: optimizer steps finished inside the blocked window,
times the batch, over the window's length. A sample is one sequence of the
configuration's length."""


def value(obs):
    return obs["steps_in_window"] * obs["batch"] / obs["window_s"]
