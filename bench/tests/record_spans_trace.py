"""Record the small chip trace with the program's spans that
``test_xplane.py`` reads.

    python bench/tests/record_spans_trace.py <out.xplane.pb>

Run from the root of a checkout on a machine with a TPU: the repo's
``BatchedCascadeEngine`` at test size (LR -> tinytf, 8 lanes) serves three
ticks inside ``bench.window``/``bench.tick`` spans.  Its expert budget is
0, so each tick is the route alone (stage A, the level walk, no expert and
no update): the device work of a tick stays a few hundred events and the
trace small.  Beside the trace it writes ``<out>.host.json``: the token
ids and token slots of the padded route-pass batches of those ticks,
counted on the host, which ``route_token_fill`` read from the trace must
equal.  The trace is written without its ``/host:metadata`` plane.
"""
import json
import sys
import tempfile
from dataclasses import replace
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))

from repro.core import (BatchedCascadeEngine, ModelExpert,  # noqa: E402
                        default_cascade_config)
from repro.data import make_stream  # noqa: E402
from repro.models.students import TinyTFSpec, tinytf_init  # noqa: E402

S = 8
# the BERT level's 512 token slots, at a test-size width: the documents'
# lengths then leave part of each row to padding, as in the cell
SPEC = TinyTFSpec(vocab=256, max_len=512, d_model=32, n_heads=2,
                  n_layers=1, d_ff=64, n_classes=2)


def _varint(buf: bytes, i: int):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def _fields(buf: bytes):
    """``(field number, start, end, payload)`` of each top-level field of
    a serialized protobuf message (payload only for length-delimited)."""
    i = 0
    while i < len(buf):
        start = i
        key, i = _varint(buf, i)
        wire = key & 7
        payload = None
        if wire == 0:
            _, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            payload, i = buf[i:i + n], i + n
        else:                             # fixed 64 or 32 bits
            i += 8 if wire == 1 else 4
        yield key >> 3, start, i, payload


def without_plane(space: bytes, name: str) -> bytes:
    """A serialized XSpace without its plane called ``name``, every other
    byte kept (``XSpace.planes`` is field 1, ``XPlane.name`` field 2)."""
    keep = []
    for field, start, end, payload in _fields(space):
        if field == 1 and any(f == 2 and p == name.encode()
                              for f, _, _, p in _fields(payload)):
            continue
        keep.append(space[start:end])
    return b"".join(keep)


def main(out: str) -> None:
    stream = make_stream("imdb", seed=0, n_samples=8 * S)
    cfg = replace(default_cascade_config(n_classes=2, mu=3e-7),
                  tf_spec=SPEC, hard_budget=0)
    expert = ModelExpert(params=tinytf_init(jax.random.PRNGKey(1), SPEC),
                         spec=SPEC)
    eng = BatchedCascadeEngine(cfg, expert, n_streams=S)
    counted = {"tokens": 0, "token_slots": 0, "on": False}
    dispatch = eng._dispatch_level

    def counting(i, fi, sel, t, calib=0):
        handles, xb = dispatch(i, fi, sel, t, calib)
        if counted["on"] and np.issubdtype(xb.dtype, np.integer):
            counted["tokens"] += int(np.count_nonzero(xb))
            counted["token_slots"] += int(xb.size)
        return handles, xb

    eng._dispatch_level = counting

    def tick(k):
        idxs = list(range(k * S, (k + 1) * S))
        return eng.process_tick(idxs, [stream.docs[i] for i in idxs])

    for k in range(4):                # compile every shape first
        tick(k)
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1    # the spans, not the runtime's events
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False  # keeps the file small
        jax.profiler.start_trace(d, profiler_options=opts)
        counted["on"] = True
        with jax.profiler.TraceAnnotation("bench.window"):
            for k in range(4, 7):
                with jax.profiler.TraceAnnotation("bench.tick"):
                    tick(k)
            jax.block_until_ready([lvl.params for lvl in eng.levels])
        counted["on"] = False
        jax.profiler.stop_trace()
        path = sorted(Path(d).rglob("*.xplane.pb"))[-1]
        # the metadata plane holds the programs' HLO, most of the file's
        # bytes, and nothing the reductions read
        Path(out).write_bytes(without_plane(path.read_bytes(),
                                            "/host:metadata"))
    eng.close()
    del counted["on"]
    Path(out + ".host.json").write_text(json.dumps(counted) + "\n")


if __name__ == "__main__":
    main(sys.argv[1])
