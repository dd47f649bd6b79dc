"""Record ``sample_v5e_boundary.xplane.pb``, the small trace that
``tests/chipbench/test_chipbench_boundary.py`` checks the step-boundary
reader on (``readers/trace_step_boundary.py``): the program's own engine at
a toy size (one block of GPT-2's head shape, 16 positions a page), the
overlapped loop on, four requests whose prompts take two chunks each and
whose outputs end at three different steps, so the recording holds mixed
steps, decode steps built after their predecessor's fetch and decode steps
dispatched ahead, under the spans the engine itself leaves (``serve.build``,
``serve.dispatch`` with ``serve.put`` / ``serve.launch``,
``serve.speculate``, ``serve.fetch``, ``serve.commit``).

    chiprun -- python3 -m chipbench.reduce.record_boundary

writes ``chiprun_out/sample_v5e_boundary.xplane.pb``; copy it beside this
file. Only a chip's trace has a device plane, so with no TPU it exits 2.

A step program of even one block is a few hundred instructions, each named
by its whole text, and a repository keeps no file of megabytes: ``trim``
keeps of the recording what the readers of ``xplane_meta`` and the
step-boundary reader read (the device planes' ``XLA Ops`` and ``XLA
Modules`` lines, a program's ``run_id``; the host plane's ``serve.*`` /
``front.*`` / ``train.*`` events and the runtime's ``DoEnqueueProgram`` /
``CompleteCallbacks`` with their stats), cuts every instruction's text to
its name and drops the device ops' own stats. Times, order, programs, spans
and their stats are as recorded."""
import glob
import os
import shutil
import sys

from chipbench.readers.trace_step_boundary import RUN_ID, RUNTIME_EVENTS
from chipbench.reduce import xplane_meta as xm

KEEP_NAME = 48      # bytes of a device instruction's text: ``%fusion.12 = ``


def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number, wire, payload):
    """One field re-encoded: ``payload`` an int (varint) or bytes."""
    head = _varint(number << 3 | wire)
    if wire == 0:
        return head + _varint(payload)
    if wire == 2:
        return head + _varint(len(payload)) + payload
    return head + payload            # fixed 32 / 64: as they were


def _copy(buf, f, wt, v):
    return _field(f, wt, v if wt == 0 else bytes(buf[v[0]:v[1]]))


def _message(buf, span, rewrite):
    """A message copied field by field; ``rewrite(f, wt, v)`` returns the
    bytes to keep for a field, ``b""`` to drop it, ``None`` to copy it."""
    out = bytearray()
    for f, wt, v in xm._fields(buf, *span):
        kept = rewrite(f, wt, v)
        out += _copy(buf, f, wt, v) if kept is None else kept
    return bytes(out)


def _trim_plane(buf, span):
    name, lines, ev_meta, stat_names = xm._plane(buf, span)
    device = name.startswith(xm.DEVICE_PLANE)
    if not device and name != xm.HOST_PLANE:
        return b""
    wanted = {mid for mid, (n, _) in ev_meta.items()
              if device or n.startswith(xm.SPAN_PREFIXES)
              or n in RUNTIME_EVENTS}
    run_id = {sid for sid, n in stat_names.items() if n == RUN_ID}
    used = set()

    def line(lspan):
        lname, _, _, events = xm._line(buf, lspan)
        if device and lname not in (xm.OPS_LINE, xm.MODULES_LINE):
            return b""
        kept = [e for e in events if xm._first_varint(buf, e[0]) in wanted]
        if not kept:
            return b""
        used.update(xm._first_varint(buf, e[0]) for e in kept)

        def event(f, wt, v):
            """A device event keeps no stat of its own but a program's run."""
            if not device or f != 4:
                return None
            return None if lname == xm.MODULES_LINE and xm._first_varint(
                buf, v[0]) in run_id else b""

        spans = {e: _message(buf, e, event) for e in kept}
        return _message(buf, lspan, lambda f, wt, v: _field(
            4, 2, spans[v]) if f == 4 and v in spans else (
                b"" if f == 4 else None))

    def meta_value(f, wt, v):       # XEventMetadata: id, a short name
        if f == 1:
            return None
        if f == 2:
            return _field(2, 2, bytes(buf[v[0]:v[1]])[
                :KEEP_NAME if device else None])
        return b"" if device else None

    def meta_entry(f, wt, v):       # a map entry: key=1, value=2
        return _field(2, 2, _message(buf, v, meta_value)) if f == 2 else None

    new_lines = [line(lspan) for lspan in lines]

    def plane(f, wt, v):
        if f == 3:
            return b""              # lines go last, re-encoded
        if f == 4:
            key, _ = xm._map_entry(buf, v)
            return _field(4, 2, _message(buf, v, meta_entry)) \
                if key in used else b""
        if f == 5 and device:       # names of stats: the one that was kept
            key, _ = xm._map_entry(buf, v)
            return None if key in run_id else b""
        return None

    body = _message(buf, span, plane)
    return body + b"".join(_field(3, 2, ln) for ln in new_lines if ln)


def trim(data) -> bytes:
    buf = memoryview(data)
    out = bytearray()
    for f, wt, v in xm._fields(buf):
        if f == 1 and wt == 2:
            plane = _trim_plane(buf, v)
            if plane:
                out += _field(1, 2, plane)
    return bytes(out)


def main():
    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("record_boundary: no TPU, no device plane to record",
              file=sys.stderr)
        return 2
    from tnn_tpu.models.gpt2 import GPT2
    from tnn_tpu.serving import InferenceEngine

    model = GPT2(vocab_size=512, max_len=128, num_layers=1, d_model=128,
                 num_heads=2)
    params = model.init(jax.random.PRNGKey(0), (1, 8))["params"]

    eng = InferenceEngine(model, params, num_blocks=32, block_size=16,
                          max_batch_size=4, max_seq_len=128, chunk_size=16,
                          prefix_cache=False, overlap=True)

    def drive():
        rng = np.random.default_rng(0)
        for n, new in ((24, 8), (17, 12), (30, 10), (20, 12)):
            eng.submit(rng.integers(0, 512, n).astype(np.int32), new)
        eng.run_until_complete()

    drive()                             # every program compiled
    out = os.path.join("chiprun_out", "boundary_trace")
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # the program's spans, not its frames
    jax.profiler.start_trace(out, profiler_options=opts)
    drive()
    jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)[0]
    dst = os.path.join("chiprun_out", "sample_v5e_boundary.xplane.pb")
    with open(pb, "rb") as f:
        whole = f.read()
    small = trim(whole)
    with open(dst, "wb") as f:
        f.write(small)
    shutil.rmtree(out, ignore_errors=True)
    meta = xm.read_bytes(small)
    print(f"record_boundary: {dst} {len(small)} bytes of {len(whole)}: "
          f"{len(meta['ops'])} ops, {len(meta['modules'])} programs, "
          f"{len(meta['spans'])} spans")
    from chipbench.readers import trace_step_boundary

    got, why = trace_step_boundary.account(
        meta, trace_step_boundary.runs_of(small))
    print(trace_step_boundary.table(got) if got else why)
    return 0


if __name__ == "__main__":
    sys.exit(main())
