#!/usr/bin/env python3
"""Drive the cascade server's main path once on a TPU, and check it.

Usage (from the root of a checkout):

    python chip_smoke.py               # one chip: phases a-d below
    python chip_smoke.py --chips 4     # lane-sharded engine on a data=4
                                       # mesh vs its one-device twin

Phases on one chip, each printing its result on its own line:

a. preflight: JAX must see a TPU.  There is no CPU fallback.
b. default ladder: ``serve_stream_batched`` on imdb, 640 items, 64
   lanes, with the stand-in LLM expert trained on the chip first.
c. kernel ladder at the full default specs: the route passes of the
   ``tinytf_flash`` and ``ssm`` levels must compile to Mosaic
   (``tpu_custom_call`` for flash, decode and ssd), the kernel path must
   match the jnp reference path at lane buckets 8 and 64 within the
   tolerances ``tests/test_kernel_levels.py`` pins, then the same
   serving call runs with ``ladder="kernel"``.
d. the engine contract: the batched engine at one lane and the
   sequential ``OnlineCascade`` on the same seed and items must agree
   bitwise (``tests/harness.py`` parity rule); a failure names the first
   divergent (tick, lane, level, attr).

The wall times printed are smoke-test wall times, compilation included,
not benchmark numbers.  Everything is generated from ``--seed``.  The
script exits non-zero at the first failed phase; only a full pass prints
the last line, ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

DATASET = "imdb"
MU = 3e-7                 # serve.py's default deferral-cost weight
# the engine-parity tests' imdb weight: students answer most items, so
# the contract checks exercise routing, not only expert calls
PARITY_MU = 3e-6
BUCKETS = (8, 64)         # smallest and largest lane buckets at 64 lanes
# kernel path vs reference path, as tests/test_kernel_levels.py pins them
PATH_TOL = {"tinytf_flash": 1e-5, "ssm": 2e-3}
LEVEL_KERNELS = {"tinytf_flash": {"flash_attention", "decode_attention"},
                 "ssm": {"ssd_scan"}}


class PhaseFailed(Exception):
    """A phase's check did not hold."""


def check(cond: bool, msg: str) -> None:
    """Fail the running phase with ``msg`` unless ``cond``."""
    if not cond:
        raise PhaseFailed(msg)


def say(phase: str, msg: str) -> None:
    """One result line of a phase."""
    print(f"[{phase}] {msg}", flush=True)


def preflight(chips: int):
    """Phase a: a TPU with at least ``chips`` devices, or exit."""
    import jax
    backend = jax.default_backend()
    check(backend == "tpu",
          f"JAX backend is {backend!r}: this smoke test needs a TPU and "
          f"has no CPU fallback")
    devs = jax.devices()
    check(len(devs) >= chips, f"need {chips} chips, JAX sees {len(devs)}")
    d = devs[0]
    say("a preflight", f"jax {jax.__version__} platform={d.platform} "
        f"kind={d.device_kind!r} count={len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def serve_phase(phase: str, ladder: str, seed: int) -> None:
    """Phases b and c: one ``serve_stream_batched`` call, checked."""
    import numpy as np

    from repro.launch.serve import serve_stream_batched
    n = 640
    t0 = time.perf_counter()
    m = serve_stream_batched(DATASET, n, MU, batch=64, expert_kind="model",
                             seed=seed, ladder=ladder, log_every=0)
    wall = time.perf_counter() - t0
    preds = np.asarray(m["predictions"])
    frac = m["expert_calls"] / n
    check(preds.shape == (n,) and bool(np.all((preds >= 0) & (preds < 2))),
          f"predictions malformed: shape {preds.shape}")
    check(np.isfinite(m["accuracy"]) and m["accuracy"] > 0.5,
          f"accuracy {m['accuracy']} is not better than chance")
    check(0 < m["expert_calls"] <= n,
          f"expert_calls {m['expert_calls']} outside (0, {n}]")
    say(phase, f"served {n} items ladder={ladder}: "
        f"accuracy={m['accuracy']} expert_call_fraction={frac} "
        f"smoke_wall_s={wall} (smoke-test wall time, compile included; "
        f"not a benchmark)")


def kernel_levels_phase(seed: int) -> None:
    """Phase c, before serving: Mosaic compile + path parity."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import OnlineCascade, kernel_cascade_config
    from repro.kernels import mosaic_kernels
    from repro.models import kernel_students as ks
    from repro.sharding import jit_route_pass

    cfg = kernel_cascade_config(n_classes=2, mu=MU, seed=seed)
    levels = {lvl.spec.kind: lvl
              for lvl in OnlineCascade(cfg, expert=None).levels}
    specs = {"tinytf_flash": cfg.tf_flash_spec, "ssm": cfg.ssm_spec}
    logits = {"tinytf_flash": ks.tinytf_flash_logits,
              "ssm": ks.ssm_student_logits}
    rng = np.random.default_rng(seed)
    for kind, want in LEVEL_KERNELS.items():
        lvl, spec = levels[kind], specs[kind]
        # the head starts at zero, which would make any parity trivial
        params = dict(lvl.params)
        params["cls_w"] = jax.random.normal(
            jax.random.PRNGKey(seed + 1), params["cls_w"].shape) * 0.1
        route = jit_route_pass(lvl.route_pass)
        kernel_logits = jax.jit(functools.partial(
            logits[kind], spec=spec, use_kernels=True))
        for b in BUCKETS:
            # pads only at the end, lengths from 1 to the whole buffer
            lens = rng.integers(1, spec.max_len + 1, b)
            lens[0], lens[-1] = 1, spec.max_len
            toks = np.zeros((b, spec.max_len), np.int32)
            for i, n in enumerate(lens):
                toks[i, :n] = rng.integers(1, spec.vocab, n)
            toks = jnp.asarray(toks)
            # the served route pass, at the precision serving compiles it
            served = route.lower(params, lvl.dparams, toks).compile()
            got = mosaic_kernels(served.as_text())
            check(want <= got, f"{kind} route pass at bucket {b}: Mosaic "
                  f"kernels {sorted(got)}, expected {sorted(want)}")
            probs_k = np.asarray(served(params, lvl.dparams, toks)[0])
            out_k = np.asarray(kernel_logits(params, toks))
            # only the reference at full f32: the TPU's default would
            # make the reference itself drift
            with jax.default_matmul_precision("float32"):
                out_r = np.asarray(
                    logits[kind](params, toks, spec, use_kernels=False))
            probs_r = np.asarray(jax.nn.softmax(out_r, axis=-1))
            err = float(np.max(np.abs(out_k - out_r)))
            perr = float(np.max(np.abs(probs_k - probs_r)))
            tol = PATH_TOL[kind]
            check(bool(np.all(np.isfinite(out_k))),
                  f"{kind} bucket {b}: non-finite kernel-path logits")
            check(np.allclose(out_k, out_r, atol=tol, rtol=tol),
                  f"{kind} bucket {b}: kernel vs reference path logits max "
                  f"abs err {err} beyond atol=rtol={tol}")
            check(np.allclose(probs_k, probs_r, atol=tol, rtol=tol),
                  f"{kind} bucket {b}: served route-pass probs vs reference "
                  f"max abs err {perr} beyond atol=rtol={tol}")
            say("c kernel levels", f"{kind} bucket={b}: tpu_custom_call "
                f"for {sorted(got & want)}; kernel-vs-ref max abs err: "
                f"logits {err}, served probs {perr} (tol {tol})")


def contract_phase(seed: int) -> None:
    """Phase d: batched engine at one lane == sequential reference."""
    from harness import (assert_run_parity, batched_engine, make_setup,
                         run_pair, sequential_engine)
    n = 200
    stream, cfg = make_setup(PARITY_MU, n, dataset=DATASET, seed=seed)
    ref = sequential_engine(cfg, stream)
    new = batched_engine(cfg, stream, n_streams=1)
    t0 = time.perf_counter()
    m_ref, m_new = run_pair(ref, new, stream)   # determinism-traced
    wall = time.perf_counter() - t0
    try:
        assert_run_parity(ref, m_ref, new, m_new,
                          history_keys=("level", "expert_called"),
                          costs=True)
    except AssertionError as err:
        raise PhaseFailed(f"batch=1 engine vs OnlineCascade: {err}")
    say("d contract", f"{n} items: predictions, levels, expert_calls "
        f"({m_ref['expert_calls']}) and state bitwise equal, batched "
        f"S=1 vs sequential; smoke_wall_s={wall} (not a benchmark)")


def lane_placement(cfg, stream, mesh, lanes: int) -> list:
    """Device ids each lane-split array is spread over, read from the
    live arrays while a pipelined sharded engine holds its in-flight
    ticks' route passes on the devices."""
    import jax

    from harness import batched_engine
    eng = batched_engine(cfg, stream, n_streams=lanes, mesh=mesh,
                         pipeline_depth=1)
    placed = []
    for start in range(0, len(stream), lanes):
        idxs = list(range(start, min(start + lanes, len(stream))))
        eng.submit_tick(idxs, [stream.docs[i] for i in idxs])
        placed += [sorted(s.device.id for s in a.addressable_shards)
                   for a in jax.live_arrays()
                   if a.ndim and not a.sharding.is_fully_replicated]
    eng.drain()
    eng.close()
    return placed


def mesh_phase(seed: int, chips: int) -> None:
    """``--chips 4``: lane-sharded engine vs its one-device twin."""
    import numpy as np

    from harness import (assert_run_parity, batched_engine, make_setup,
                         run_pair)
    from repro.launch.mesh import make_mesh
    n, lanes = 640, 64
    stream, cfg = make_setup(PARITY_MU, n, dataset=DATASET, seed=seed)
    mesh = make_mesh((chips, 1), ("data", "model"))
    base = batched_engine(cfg, stream, n_streams=lanes)
    shard = batched_engine(cfg, stream, n_streams=lanes, mesh=mesh)
    t0 = time.perf_counter()
    m0, m1 = run_pair(base, shard, stream)
    wall = time.perf_counter() - t0
    try:
        assert_run_parity(base, m0, shard, m1, state="allclose",
                          attrs=("params", "dparams"),
                          history_keys=("level", "expert_called"))
        np.testing.assert_array_equal(base.expert_calls, shard.expert_calls)
    except AssertionError as err:
        raise PhaseFailed(f"data={chips} mesh vs one device: {err}")
    all_ids = sorted(d.id for d in mesh.devices.flat)
    placed = lane_placement(cfg, stream, mesh, lanes)
    check(bool(placed) and all(p == all_ids for p in placed),
          f"lane-split arrays of in-flight ticks on devices {placed}, "
          f"expected each split over all of {all_ids}")
    say("mesh", f"{lanes} lanes x {n} items on mesh {dict(mesh.shape)}: "
        f"predictions, levels, expert_calls ({m1['expert_calls']}) equal, "
        f"params/dparams allclose; {len(placed)} lane-split arrays of "
        f"in-flight ticks, each over devices {all_ids}; "
        f"smoke_wall_s={wall} (not a benchmark)")


def main() -> int:
    """Run the phases; returns the process exit code."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the lane-sharded mesh check")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke.py must run from a checkout of the repo "
              f"(no src/repro beside {ROOT})", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "tests")]
    from repro.launch.compile_cache import enable_compile_cache

    phases = [("a preflight", lambda: preflight(args.chips))]
    if args.chips == 1:
        phases += [
            ("b default ladder",
             lambda: serve_phase("b default ladder", "default", args.seed)),
            ("c kernel levels", lambda: kernel_levels_phase(args.seed)),
            ("c kernel ladder",
             lambda: serve_phase("c kernel ladder", "kernel", args.seed)),
            ("d contract", lambda: contract_phase(args.seed)),
        ]
    else:
        phases += [("mesh", lambda: mesh_phase(args.seed, args.chips))]
    enable_compile_cache()
    device = None
    for name, run in phases:
        try:
            out = run()
        except PhaseFailed as err:
            say(name, f"FAILED: {err}")
            return 1
        device = device or out
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
