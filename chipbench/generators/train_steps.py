"""Generator ``train_steps``: a training job has no arrivals. Steps are
dispatched back to back for the window, as the CLI's loop dispatches them, and
the window closes when everything dispatched has finished
(``jax.block_until_ready``): the steps counted are steps done, over all the
time they took. Batch, sequence length and the fetch interval are the
configuration's; the mix has no parameter of its own."""
import time


def drive(job, traffic, ctx):
    ctx.window_opened()
    t_end = ctx.t_open + ctx.seconds
    while time.perf_counter() < t_end:
        job.step()
    job.sync()
    ctx.window_closed()
