"""Record ``sample_v5e_scoped.xplane.pb``, the small trace that
``tests/chipbench/test_chipbench_spans.py`` checks the scope, module and
host-span readers on: a jitted program named ``tnn_sample`` with two scopes
(``attn_qkv`` a matmul, ``paged_attn`` a Pallas kernel named
``tnn_sample_kernel``), run three times under the spans a serving step
leaves (``serve.build`` with a nested ``serve.admit``, ``serve.dispatch``,
``serve.fetch``), with a sleep no span covers between the steps.

    chiprun -- python3 -m chipbench.reduce.record_sample

writes ``chiprun_out/sample_v5e_scoped.xplane.pb``; copy it beside this file.
Only a chip's trace has a device plane, so with no TPU it exits 2."""
import glob
import os
import shutil
import sys
import time


def main():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.profiler import TraceAnnotation as span

    if jax.devices()[0].platform != "tpu":
        print("record_sample: no TPU, no device plane to record",
              file=sys.stderr)
        return 2

    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def tnn_sample(x, w):
        with jax.named_scope("attn_qkv"):
            y = jnp.tanh(x @ w)
        with jax.named_scope("paged_attn"):
            return pl.pallas_call(
                double, name="tnn_sample_kernel",
                out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype))(y)

    f = jax.jit(tnn_sample)
    x = jnp.ones((256, 512), jnp.float32)
    w = jnp.ones((512, 512), jnp.float32) * 0.01
    f(x, w).block_until_ready()

    out = os.path.join("chiprun_out", "sample_trace")
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(out)
    for i in range(3):
        with span("serve.build", step=i):
            with span("serve.admit", rid=i, step=i):
                time.sleep(0.0005)
            time.sleep(0.0005)
        with span("serve.dispatch", step=i, kind="decode", key="sample"):
            y = f(x, w)
        with span("serve.fetch", step=i):
            y.block_until_ready()
        time.sleep(0.001)          # idle that no span names
    jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)[0]
    dst = os.path.join("chiprun_out", "sample_v5e_scoped.xplane.pb")
    shutil.copy(pb, dst)
    shutil.rmtree(out, ignore_errors=True)
    print(f"record_sample: {dst} {os.path.getsize(dst)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
