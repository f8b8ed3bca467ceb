"""Lane-sharded vs single-device cascade serving throughput.

Measures `BatchedCascadeEngine` on a `data=N` virtual-device mesh against
the same engine on `data=1`, in a subprocess carrying the XLA
device-count flag (the parent process keeps its single device).  Two
regimes:

* ``converged`` — the compute-bound steady state after the gates close:
  a deep dense (MLP) student serves every lane, no expert traffic and no
  updates.  This is where lane sharding pays: the per-tick forward over
  S lanes partitions into N independent per-device programs with no
  collectives in the serving path.
* ``learning`` — online-learning regime (expert calls + student/deferral
  updates active).  The update steps run replicated (the cascade state
  is shared), so this regime scales worse — reported honestly.

Measurement methodology (this host virtualizes N devices onto few
physical cores, and wall-clock on a shared box is noisy):

* wall-clock items/sec for data=1 and data=N are timed **interleaved**
  (alternating repetitions, median of paired ratios) so machine-load
  drift cancels;
* the ``projected`` figure times the *actual per-device program* (the
  per-level jitted forward at bucket S/N) against the full-bucket
  program on one device, in the same process back-to-back, and projects
  the tick speedup a real N-device mesh realizes when each device runs
  its lane shard concurrently:

      projected_speedup = (t_host + t_jit_full) / (t_host + t_jit_shard)

  Virtual CPU devices share this host's cores, so measured wall-clock
  under-reports that concurrency; both numbers are always printed.

CSV convention: name,us_per_call,derived.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

SUBPROC_SNIPPET = """
import os
ndev, S, n, reps, seed = (PARAMS["ndev"], PARAMS["batch"],
                          PARAMS["samples"], PARAMS["reps"],
                          PARAMS["seed"])
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=%d" % ndev)
import sys, time, json
sys.path.insert(0, PARAMS["src"])
import numpy as np
from dataclasses import replace
from repro.core import (BatchedCascadeEngine, SimulatedExpert,
                        default_cascade_config)
from repro.core.cascade import LevelSpec
from repro.models.students import MLPSpec
from repro.data import make_stream
from repro.launch.mesh import make_mesh

stream = make_stream("hatespeech", seed=seed, n_samples=n)
base = default_cascade_config(n_classes=stream.spec.n_classes, mu=3e-7,
                              seed=seed)

# converged regime: one deep dense student serves every lane
# (hard_budget=0 suppresses jumps and expert calls — the post-closure
# steady state, which is pure batched student forwards)
mlp_level = LevelSpec(kind="mlp", cost=120.0, cache_size=32, batch_size=16,
                      student_lr=1e-3, beta_decay=0.95,
                      calibration_factor=0.3)
conv_cfg = replace(base, levels=(mlp_level,), hard_budget=0,
                   mlp_spec=MLPSpec(hidden=1024, n_layers=8))
# learning regime: the default cascade with slow DAgger decay (expert
# calls and online updates active throughout)
learn_cfg = replace(base, levels=tuple(
    replace(lvl, beta_decay=0.995) for lvl in base.levels))


def engine(cfg, nd):
    mesh = make_mesh((nd, 1), ("data", "model"))
    e = BatchedCascadeEngine(cfg, SimulatedExpert(stream, "gpt-3.5-turbo"),
                             n_streams=S, mesh=mesh)
    e.run(stream)        # compile + warm
    e.reset()
    return e


def paired_rates(cfg):
    e1, eN = engine(cfg, 1), engine(cfg, ndev)
    r1s, rNs, ratios = [], [], []
    for _ in range(reps):          # interleaved: load drift cancels
        t0 = time.time(); e1.run(stream); a = n / (time.time() - t0)
        e1.reset()
        t0 = time.time(); eN.run(stream); b = n / (time.time() - t0)
        eN.reset()
        r1s.append(a); rNs.append(b); ratios.append(b / a)
    return e1, (float(np.median(r1s)), float(np.median(rNs)),
                float(np.median(ratios)))


def projection(e1):
    # time the per-level jitted forward at the full bucket vs the
    # per-device shard bucket, same device, INTERLEAVED (alternating
    # pairs, median of paired ratios) so host-load drift cancels just
    # like the wall-clock measurement
    lvl = e1.levels[0]
    fi = np.stack([lvl.featurize(stream.docs[i]) for i in range(S)])
    pd = e1._predict_defer[0]
    xb_full = e1._put_lane(fi)
    xb_shard = e1._put_lane(fi[: max(S // ndev, 1)])
    pd(lvl.params, lvl.dparams, xb_full)[0].block_until_ready()
    pd(lvl.params, lvl.dparams, xb_shard)[0].block_until_ready()

    def one(xb, calls=8):
        t0 = time.time()
        for _ in range(calls):
            p, d = pd(lvl.params, lvl.dparams, xb)
        p.block_until_ready()
        return (time.time() - t0) / calls

    fulls, shards = [], []
    for _ in range(max(reps, 5)):
        fulls.append(one(xb_full))
        shards.append(one(xb_shard))
    t_full = float(np.median(fulls))
    t_shard = float(np.median(shards))
    # non-jit share of a tick (featurize, RNG, masks, transfers)
    t0 = time.time()
    e1.run(stream)
    tick_wall = (time.time() - t0) / (n / S)
    e1.reset()
    t_host = max(tick_wall - t_full, 0.0)
    ratios = sorted((t_host + f) / (t_host + s)
                    for f, s in zip(fulls, shards))
    return (float(np.median(ratios)),
            {"t_jit_full_ms": t_full * 1e3, "t_jit_shard_ms": t_shard * 1e3,
             "t_host_ms": t_host * 1e3})


out = {"ndev": ndev, "batch": S, "samples": n}
e1, (r1, rN, wall) = paired_rates(conv_cfg)
proj, detail = projection(e1)
out["converged"] = {
    "data1_items_per_sec": r1, f"data{ndev}_items_per_sec": rN,
    "wall_speedup": wall, "projected_speedup": proj,
    f"data{ndev}_projected_items_per_sec": r1 * proj, **detail,
}
_, (r1l, rNl, walll) = paired_rates(learn_cfg)
out["learning"] = {
    "data1_items_per_sec": r1l, f"data{ndev}_items_per_sec": rNl,
    "wall_speedup": walll,
}
print("RESULT " + json.dumps(out))
"""


def run(samples: int = 512, seed: int = 0, devices: int = 8,
        batch: int = 64, quick: bool = False) -> dict:
    if quick:
        samples = min(samples, 256)
    reps = 3 if quick else 5
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    params = dict(ndev=devices, src=src, batch=batch, samples=samples,
                  seed=seed, reps=reps)
    code = f"PARAMS = {params!r}\n" + SUBPROC_SNIPPET
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    # virtual CPU devices by design; and a chip belongs to one process,
    # which may be this parent (benchmarks/run.py imports jax)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=3000,
                          env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"sharded_throughput subprocess failed:\n"
                           f"{proc.stderr[-3000:]}")
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    res = json.loads(line[len("RESULT "):])

    c, le = res["converged"], res["learning"]
    nd = res["ndev"]
    print(f"[sharded_throughput] converged batch={batch} "
          f"data1={c['data1_items_per_sec']:8.1f} it/s  "
          f"data{nd}={c[f'data{nd}_items_per_sec']:8.1f} it/s "
          f"(wall {c['wall_speedup']:.2f}x)")
    print(f"[sharded_throughput] converged projected on a real "
          f"{nd}-device mesh: "
          f"{c[f'data{nd}_projected_items_per_sec']:8.1f} it/s "
          f"({c['projected_speedup']:.2f}x; per-device shard "
          f"{c['t_jit_shard_ms']:.1f}ms vs full bucket "
          f"{c['t_jit_full_ms']:.1f}ms + host {c['t_host_ms']:.1f}ms)")
    print(f"[sharded_throughput] learning  batch={batch} "
          f"data1={le['data1_items_per_sec']:8.1f} it/s  "
          f"data{nd}={le[f'data{nd}_items_per_sec']:8.1f} it/s "
          f"(wall {le['wall_speedup']:.2f}x; updates replicated)")
    return res


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--samples", type=int, default=512)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    run(samples=args.samples, seed=args.seed, devices=args.devices,
        batch=args.batch, quick=args.quick)
