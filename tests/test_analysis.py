"""cascade-lint suite: each checker catches its bug class, the good twin
stays clean, the suppression/baseline machinery works, and THE TREE IS
CLEAN under --strict.

The regression fixtures at the bottom are the acceptance contract: the
PR-1 salted-``hash()`` seeding bug and an unguarded ``ExpertTicket``
access are re-introduced into the *real* module sources and must be
caught — that is what the CI `analysis` job guards.
"""
from __future__ import annotations

import ast
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis import (
    ALL_RULES, fingerprint, load_baseline, render_baseline, run_analysis)
from repro.analysis.cli import _render_github, find_repo_root, main
from repro.analysis.engine import Finding, ModuleContext
from repro.analysis.rules import (
    DeterminismRule, DocsContractRule, JitPurityRule, KernelContractRule,
    LockDisciplineRule, RngDisciplineRule, RngFlowRule,
    ShardingContractRule)

REPO_ROOT = Path(__file__).resolve().parents[1]


def ctx_for(src: str, rel: str = "src/repro/core/sample.py",
            root: Path = REPO_ROOT) -> ModuleContext:
    src = textwrap.dedent(src)
    return ModuleContext(root=root, path=root / rel, rel=rel, source=src,
                         lines=src.splitlines(), tree=ast.parse(src))


def run_rule(rule, src: str, rel: str = "src/repro/core/sample.py"):
    return list(rule.check_module(ctx_for(src, rel)))


# ---------------------------------------------------------------------------
# CAS001 — RNG discipline
# ---------------------------------------------------------------------------
class TestRngDiscipline:
    def test_per_tick_construction_in_core_flagged(self):
        bad = """
            import numpy as np
            class Engine:
                def process_tick(self, t):
                    rng = np.random.default_rng(self.seed * 1000 + t)
                    return rng.uniform()
        """
        fs = run_rule(RngDisciplineRule(), bad, "src/repro/core/batched.py")
        assert len(fs) == 1 and fs[0].rule == "CAS001"
        assert "tick_rngs" in fs[0].message

    def test_tick_rngs_usage_is_clean(self):
        good = """
            from repro.core.rng import sample_cache_indices, tick_rngs
            class Engine:
                def process_tick(self, t):
                    rngs = tick_rngs(self.seed, 0, t, n_levels=2)
                    return sample_cache_indices(rngs.cache[0], 8, 4)
        """
        assert run_rule(RngDisciplineRule(), good,
                        "src/repro/core/batched.py") == []

    def test_init_and_training_contexts_exempt(self):
        good = """
            import jax
            import numpy as np
            class Engine:
                def __init__(self, config):
                    self.key = jax.random.PRNGKey(config.seed)
            def train_expert(seed):
                return np.random.default_rng(seed)
        """
        assert run_rule(RngDisciplineRule(), good,
                        "src/repro/core/batched.py") == []

    def test_unseeded_construction_flagged_everywhere(self):
        bad = """
            from numpy.random import default_rng
            def demo():
                return default_rng().integers(0, 10)
        """
        fs = run_rule(RngDisciplineRule(), bad, "examples/demo.py")
        assert len(fs) == 1 and "unseeded" in fs[0].message

    def test_seeded_construction_outside_core_clean(self):
        good = """
            import numpy as np
            def bench(seed=0):
                return np.random.default_rng(seed).normal(size=4)
        """
        assert run_rule(RngDisciplineRule(), good, "benchmarks/b.py") == []

    def test_whitelisted_core_module_clean(self):
        src = """
            import numpy as np
            def tick_rngs(seed, s, t):
                return np.random.default_rng(
                    np.random.SeedSequence((seed, s, t)))
        """
        assert run_rule(RngDisciplineRule(), src,
                        "src/repro/core/rng.py") == []


# ---------------------------------------------------------------------------
# CAS002 — determinism hazards
# ---------------------------------------------------------------------------
class TestDeterminism:
    def test_pr1_salted_hash_seeding_bug_regression(self):
        # the exact bug class PR 1 fixed in make_stream/expert_labels:
        # builtin hash() of an f-string fed a generator seed, so corpora
        # changed between processes (PYTHONHASHSEED salting)
        bad = """
            import numpy as np
            def make_stream(name, seed):
                rng = np.random.default_rng(
                    abs(hash(f"{seed}:{name}")) % (2 ** 31))
                return rng.permutation(100)
        """
        fs = run_rule(DeterminismRule(), bad, "src/repro/data/streams.py")
        assert len(fs) == 1 and fs[0].rule == "CAS002"
        assert "salted" in fs[0].message and "crc32" in fs[0].message

    def test_crc32_twin_is_clean(self):
        good = """
            import zlib
            import numpy as np
            def make_stream(name, seed):
                rng = np.random.default_rng(
                    zlib.crc32(f"{seed}:{name}".encode()))
                return rng.permutation(100)
        """
        assert run_rule(DeterminismRule(), good,
                        "src/repro/data/streams.py") == []

    def test_wall_clock_seed_flagged(self):
        fs = run_rule(DeterminismRule(), """
            import time
            import numpy as np
            rng = np.random.default_rng(int(time.time()))
        """, "benchmarks/b.py")
        assert len(fs) == 1 and "time.time" in fs[0].message

    def test_seed_variable_from_urandom_flagged(self):
        fs = run_rule(DeterminismRule(), """
            import os
            seed = int.from_bytes(os.urandom(4), "little")
        """, "benchmarks/b.py")
        assert len(fs) == 1 and "os.urandom" in fs[0].message

    def test_timing_measurement_is_clean(self):
        good = """
            import time
            def bench(fn):
                t0 = time.time()
                fn()
                return time.time() - t0
        """
        assert run_rule(DeterminismRule(), good, "benchmarks/b.py") == []

    def test_legacy_global_sampler_flagged(self):
        fs = run_rule(DeterminismRule(), """
            import numpy as np
            noise = np.random.randn(8)
        """, "examples/e.py")
        assert len(fs) == 1 and "global-state" in fs[0].message

    def test_id_sort_key_flagged(self):
        fs = run_rule(DeterminismRule(), """
            def order(objs):
                return sorted(objs, key=id)
        """)
        assert len(fs) == 1 and "id()" in fs[0].message

    def test_set_iteration_flagged_sorted_clean(self):
        fs = run_rule(DeterminismRule(), """
            for name in {"imdb", "hatespeech"}:
                print(name)
        """, "benchmarks/b.py")
        assert len(fs) == 1 and "set" in fs[0].message
        good = """
            for name in sorted({"imdb", "hatespeech"}):
                print(name)
        """
        assert run_rule(DeterminismRule(), good, "benchmarks/b.py") == []


# ---------------------------------------------------------------------------
# CAS003 — jit purity
# ---------------------------------------------------------------------------
class TestJitPurity:
    def test_self_mutation_in_jitted_method_flagged(self):
        fs = run_rule(JitPurityRule(), """
            import jax
            class Engine:
                @jax.jit
                def step(self, x):
                    self.calls += 1
                    return x * 2
        """)
        assert any("mutates self.calls" in f.message for f in fs)

    def test_item_and_tracer_cast_flagged(self):
        fs = run_rule(JitPurityRule(), """
            import jax
            def loss(params, batch):
                return (params * batch).sum()
            step = jax.jit(loss)
            @jax.jit
            def bad(x):
                return float(x) + x.sum().item()
        """)
        msgs = " | ".join(f.message for f in fs)
        assert ".item()" in msgs and "float()" in msgs

    def test_static_args_exempt_from_cast_check(self):
        good = """
            import functools
            import jax
            @functools.partial(jax.jit, static_argnames=("block",))
            def op(x, *, block):
                return x.reshape(int(block), -1)
        """
        assert run_rule(JitPurityRule(), good) == []

    def test_pure_jitted_fn_clean(self):
        good = """
            import jax
            import jax.numpy as jnp
            @jax.jit
            def step(params, grads):
                return jax.tree_util.tree_map(
                    lambda p, g: p - 0.1 * g, params, grads)
        """
        assert run_rule(JitPurityRule(), good) == []

    def test_donated_buffer_read_after_call_flagged(self):
        fs = run_rule(JitPurityRule(), """
            import jax
            def scatter(buf, x):
                return buf.at[0].set(x)
            step = jax.jit(scatter, donate_argnums=(0,))
            def run(buf, x):
                out = step(buf, x)
                return buf.sum() + out.sum()
        """)
        assert len(fs) == 1 and "donated" in fs[0].message

    def test_donated_buffer_reassigned_clean(self):
        good = """
            import jax
            def scatter(buf, x):
                return buf.at[0].set(x)
            step = jax.jit(scatter, donate_argnums=(0,))
            def run(buf, x):
                buf = step(buf, x)
                return buf.sum()
        """
        assert run_rule(JitPurityRule(), good) == []

    def test_repo_jit_factory_convention_staged(self):
        fs = run_rule(JitPurityRule(), """
            from repro.sharding.specs import jit_route_pass
            class Level:
                def make(self):
                    def route(self, feats):
                        self.count += 1
                        return feats
                    return jit_route_pass(route, None)
        """)
        assert any("mutates self.count" in f.message for f in fs)


# ---------------------------------------------------------------------------
# CAS004 — lock discipline
# ---------------------------------------------------------------------------
_TICKET_TEMPLATE = """
    import threading
    class Ticket:
        def __init__(self):
            self._lock = threading.RLock()
            self._shards = []   # guarded-by: _lock
        def done(self):
            {done_body}
        def add(self, s):
            with self._lock:
                self._shards.append(s)
"""


class TestLockDiscipline:
    def test_unguarded_read_flagged(self):
        bad = textwrap.dedent(_TICKET_TEMPLATE).format(
            done_body="return all(s.done() for s in self._shards)")
        fs = run_rule(LockDisciplineRule(), bad)
        assert len(fs) == 1 and fs[0].rule == "CAS004"
        assert "_shards" in fs[0].message and "_lock" in fs[0].message

    def test_guarded_access_clean(self):
        good = textwrap.dedent(_TICKET_TEMPLATE).format(
            done_body="""with self._lock:
                return all(s.done() for s in self._shards)""")
        assert run_rule(LockDisciplineRule(), good) == []

    def test_constructor_family_exempt(self):
        src = """
            import threading
            class T:
                def __init__(self):
                    self._lock = threading.RLock()
                    self._q = []   # guarded-by: _lock
                    self._q.append(0)
                def __del__(self):
                    self._q.clear()
        """
        assert run_rule(LockDisciplineRule(), src) == []

    def test_unannotated_class_ignored(self):
        src = """
            class Plain:
                def __init__(self):
                    self._shards = []
                def peek(self):
                    return self._shards
        """
        assert run_rule(LockDisciplineRule(), src) == []

    def test_real_experts_module_conforms(self):
        src = (REPO_ROOT / "src/repro/core/experts.py").read_text()
        fs = run_rule(LockDisciplineRule(), src, "src/repro/core/experts.py")
        assert fs == []

    def test_regression_unguarding_real_ticket_is_caught(self):
        # strip ONE lock enclosure from the real ExpertTicket — the
        # acceptance fixture: this is exactly the edit the CI job must
        # refuse
        src = (REPO_ROOT / "src/repro/core/experts.py").read_text()
        broken = src.replace(
            """        with self._lock:
            return all([self._shard_done(s) for s in self._shards])""",
            """        return all([self._shard_done(s) for s in self._shards])""")
        assert broken != src, "ExpertTicket.done() body changed upstream"
        fs = run_rule(LockDisciplineRule(), broken,
                      "src/repro/core/experts.py")
        assert any(f.rule == "CAS004" and "_shards" in f.message
                   for f in fs)


# ---------------------------------------------------------------------------
# CAS005 — kernel/level contract (fixture tree)
# ---------------------------------------------------------------------------
def _write_kernel_pkg(root: Path, ops_src: str, ref_src: str,
                      init_src: str, kernel_src: str = None):
    pkg = root / "src/repro/kernels/toyop"
    pkg.mkdir(parents=True)
    (pkg / "kernel.py").write_text(kernel_src or textwrap.dedent("""
        def toyop_tiled(x, w):
            return x @ w
    """))
    (pkg / "ops.py").write_text(textwrap.dedent(ops_src))
    (pkg / "ref.py").write_text(textwrap.dedent(ref_src))
    (pkg / "__init__.py").write_text(textwrap.dedent(init_src))
    return pkg


class TestKernelContract:
    GOOD_OPS = """
        from repro.kernels.toyop.kernel import toyop_tiled
        def toyop(x, w, *, interpret=None):
            return toyop_tiled(x, w)
    """
    GOOD_REF = """
        def toyop_ref(x, w):
            return x @ w
    """
    GOOD_INIT = """
        from repro.kernels.toyop.ops import toyop
        __all__ = ["toyop"]
    """

    def _findings(self, tmp_path):
        res = run_analysis(tmp_path, paths=["src"],
                           rules=[KernelContractRule()])
        return res.findings

    def test_conforming_package_clean(self, tmp_path):
        _write_kernel_pkg(tmp_path, self.GOOD_OPS, self.GOOD_REF,
                          self.GOOD_INIT)
        assert self._findings(tmp_path) == []

    def test_missing_ref_twin_flagged(self, tmp_path):
        _write_kernel_pkg(tmp_path, self.GOOD_OPS, """
            def toyop_ref(x, w, scale):
                return x @ w * scale
        """, self.GOOD_INIT)
        fs = self._findings(tmp_path)
        assert len(fs) == 1 and "ref.py twin" in fs[0].message

    def test_missing_all_export_flagged(self, tmp_path):
        _write_kernel_pkg(tmp_path, self.GOOD_OPS, self.GOOD_REF, """
            from repro.kernels.toyop.ops import toyop
            __all__ = []
        """)
        fs = self._findings(tmp_path)
        assert len(fs) == 1 and "__all__" in fs[0].message

    def test_unconsumed_kernel_entry_flagged(self, tmp_path):
        _write_kernel_pkg(tmp_path, """
            def toyop(x, w, *, interpret=None):
                return x @ w
        """, self.GOOD_REF, self.GOOD_INIT)
        fs = self._findings(tmp_path)
        assert len(fs) == 1 and "not consumed by ops.py" in fs[0].message

    def test_level_kind_without_flop_model_flagged(self, tmp_path):
        (tmp_path / "src/repro/metrics").mkdir(parents=True)
        (tmp_path / "src/repro/metrics/costs.py").write_text(
            "def lr_flops(spec):\n    return 1.0\n")
        (tmp_path / "src/repro/core").mkdir(parents=True)
        (tmp_path / "src/repro/core/cascade.py").write_text(textwrap.dedent(
            """
            def config(LevelSpec):
                return [LevelSpec(kind="lr", cost=1.0),
                        LevelSpec(kind="quantum", cost=9.9)]
            """))
        fs = self._findings(tmp_path)
        assert len(fs) == 1 and "'quantum'" in fs[0].message

    def test_real_tree_conforms(self):
        res = run_analysis(REPO_ROOT, paths=["src"],
                           rules=[KernelContractRule()])
        assert res.findings == []


# ---------------------------------------------------------------------------
# CAS006 — docs contract (fixture tree)
# ---------------------------------------------------------------------------
class TestDocsContract:
    def _tree(self, tmp_path, readme: str):
        (tmp_path / "benchmarks").mkdir()
        (tmp_path / "benchmarks/speed.py").write_text("x = 1\n")
        (tmp_path / "examples").mkdir()
        (tmp_path / "examples/demo.py").write_text("x = 1\n")
        for doc in ("ARCHITECTURE.md", "MODELS.md", "ANALYSIS.md"):
            (tmp_path / "docs").mkdir(exist_ok=True)
            (tmp_path / f"docs/{doc}").write_text("stub\n")
        (tmp_path / "README.md").write_text(textwrap.dedent(readme))

    FULL_README = """
        All of benchmarks/speed.py and examples/demo.py, documented in
        docs/ARCHITECTURE.md, docs/MODELS.md and docs/ANALYSIS.md.
    """

    def _findings(self, tmp_path):
        res = run_analysis(tmp_path, paths=["benchmarks", "examples"],
                           rules=[DocsContractRule()])
        return res.findings

    def test_complete_readme_clean(self, tmp_path):
        self._tree(tmp_path, self.FULL_README)
        assert self._findings(tmp_path) == []

    def test_unmentioned_example_flagged(self, tmp_path):
        self._tree(tmp_path, """
            Only benchmarks/speed.py here, plus docs/ARCHITECTURE.md,
            docs/MODELS.md and docs/ANALYSIS.md.
        """)
        fs = self._findings(tmp_path)
        assert len(fs) == 1 and "examples/demo.py" in fs[0].message

    def test_token_match_rejects_substring(self, tmp_path):
        # "batched_speed.py" must NOT satisfy the mention of "speed.py"
        self._tree(tmp_path, """
            benchmarks/batched_speed.py and examples/demo.py;
            docs/ARCHITECTURE.md docs/MODELS.md docs/ANALYSIS.md
        """)
        fs = self._findings(tmp_path)
        assert len(fs) == 1 and "speed.py" in fs[0].message

    def test_missing_doc_flagged(self, tmp_path):
        self._tree(tmp_path, self.FULL_README)
        (tmp_path / "docs/ANALYSIS.md").unlink()
        fs = self._findings(tmp_path)
        assert len(fs) == 1 and "docs/ANALYSIS.md is missing" in \
            fs[0].message


# ---------------------------------------------------------------------------
# CAS007 — interprocedural tick-RNG dataflow (fixture tree)
# ---------------------------------------------------------------------------
def _write_core_module(root: Path, src: str, name: str = "engine.py"):
    pkg = root / "src/repro/core"
    pkg.mkdir(parents=True, exist_ok=True)
    (pkg / name).write_text(textwrap.dedent(src))


class TestRngFlow:
    def _findings(self, tmp_path):
        res = run_analysis(tmp_path, paths=["src"], rules=[RngFlowRule()])
        return res.findings

    def test_double_draw_same_purpose_flagged(self, tmp_path):
        _write_core_module(tmp_path, """
            from repro.core.rng import tick_rngs
            class Engine:
                def process_tick(self, t):
                    r = tick_rngs(self.seed, 0, t, n_levels=2)
                    u1 = r.jump.random(2)
                    u2 = r.jump.random(2)
                    return u1 + u2
        """)
        fs = self._findings(tmp_path)
        assert len(fs) == 1 and fs[0].rule == "CAS007"
        assert "consumed again" in fs[0].message
        assert "'r.jump'" in fs[0].message

    def test_draw_plus_consuming_callee_flagged(self, tmp_path):
        # interprocedural half of the reuse check: helper() draws from
        # its parameter (the summary pass must discover that), so passing
        # r.jump after drawing from it directly is a second consumption
        _write_core_module(tmp_path, """
            from repro.core.rng import tick_rngs
            def helper(gen):
                return gen.random(4)
            class Engine:
                def process_tick(self, t):
                    r = tick_rngs(self.seed, 0, t, n_levels=2)
                    u = r.jump.random(2)
                    return u + helper(r.jump)
        """)
        fs = self._findings(tmp_path)
        assert len(fs) == 1 and "passed to helper()" in fs[0].message

    def test_transitive_consumer_chain_resolved(self, tmp_path):
        # helper -> inner -> draw: the summary fixpoint must propagate
        # consumption through TWO call hops before the reuse is visible
        _write_core_module(tmp_path, """
            from repro.core.rng import tick_rngs
            def inner(gen):
                return gen.integers(0, 8)
            def helper(gen):
                return inner(gen)
            class Engine:
                def process_tick(self, t):
                    r = tick_rngs(self.seed, 0, t, n_levels=2)
                    a = helper(r.cache[0])
                    b = helper(r.cache[0])
                    return a + b
        """)
        fs = self._findings(tmp_path)
        assert len(fs) == 1 and "'r.cache[0]'" in fs[0].message

    def test_escape_to_self_flagged(self, tmp_path):
        _write_core_module(tmp_path, """
            from repro.core.rng import tick_rngs
            class Engine:
                def process_tick(self, t):
                    r = tick_rngs(self.seed, 0, t, n_levels=2)
                    self._rng = r.action
                    return self._rng.random()
        """)
        fs = self._findings(tmp_path)
        assert any("escapes" in f.message and "self._rng" in f.message
                   for f in fs)

    def test_escape_via_storing_callee_flagged(self, tmp_path):
        # the store is one call away: stash() assigns its parameter to
        # self, so passing a purpose into it caches live generator state
        _write_core_module(tmp_path, """
            from repro.core.rng import tick_rngs
            class Engine:
                def stash(self, gen):
                    self._gen = gen
                def process_tick(self, t):
                    r = tick_rngs(self.seed, 0, t, n_levels=2)
                    self.stash(r.action)
                    return 0
        """)
        fs = self._findings(tmp_path)
        assert any("escapes" in f.message and "stash()" in f.message
                   for f in fs)

    def test_one_consumer_per_purpose_clean(self, tmp_path):
        # the good twin mirrors the real engines: one draw per purpose,
        # record-class transport exempt, unknown consumers count once
        _write_core_module(tmp_path, """
            from repro.core.rng import sample_cache_indices, tick_rngs
            class TickRecord:
                pass
            class Engine:
                def process_tick(self, t):
                    r = tick_rngs(self.seed, 0, t, n_levels=2)
                    u = r.jump.random(2)
                    rec = TickRecord(r.action)
                    for i in range(2):
                        sample_cache_indices(r.cache[i], 8, 4)
                    return u, rec
        """)
        assert self._findings(tmp_path) == []

    def test_real_core_tree_conforms(self):
        res = run_analysis(REPO_ROOT, paths=["src"], rules=[RngFlowRule()])
        assert res.findings == [], "\n".join(
            f.render() for f in res.findings)


# ---------------------------------------------------------------------------
# CAS008 — sharding-spec consistency (fixture tree)
# ---------------------------------------------------------------------------
class TestShardingContract:
    SPECS = """
        import jax
        def lane_spec(mesh):
            return None
        def put_lanes(x, mesh=None):
            return x
        def jit_scatter(fn):
            return jax.jit(fn, donate_argnums=(0,))
        def jit_update(fn):
            return jax.jit(fn, donate_argnums=(0, 1))
    """
    INIT = """
        from repro.sharding.specs import (jit_scatter, jit_update,
                                          lane_spec, put_lanes)
        __all__ = ["lane_spec", "put_lanes", "jit_scatter", "jit_update"]
    """

    def _tree(self, tmp_path, core_src: str):
        pkg = tmp_path / "src/repro/sharding"
        pkg.mkdir(parents=True)
        (pkg / "specs.py").write_text(textwrap.dedent(self.SPECS))
        (pkg / "__init__.py").write_text(textwrap.dedent(self.INIT))
        _write_core_module(tmp_path, core_src, "batched.py")

    def _findings(self, tmp_path):
        res = run_analysis(tmp_path, paths=["src"],
                           rules=[ShardingContractRule()])
        return res.findings

    def test_conforming_core_clean(self, tmp_path):
        self._tree(tmp_path, """
            from repro.sharding import jit_scatter, put_lanes
            class Engine:
                def __init__(self, fn):
                    self._scatter = jit_scatter(fn)
                    self._cache = put_lanes([0.0])
                def step(self):
                    out = self._scatter(self._cache)
                    self._cache = out
                    return out
        """)
        assert self._findings(tmp_path) == []

    def test_import_of_missing_helper_flagged(self, tmp_path):
        self._tree(tmp_path, """
            from repro.sharding import put_lanes_v2
            x = put_lanes_v2([0.0])
        """)
        fs = self._findings(tmp_path)
        assert any("no such helper" in f.message for f in fs)

    def test_unexported_helper_flagged(self, tmp_path):
        pkg = tmp_path / "src/repro/sharding"
        pkg.mkdir(parents=True)
        (pkg / "specs.py").write_text(textwrap.dedent(self.SPECS))
        (pkg / "__init__.py").write_text(textwrap.dedent("""
            from repro.sharding.specs import lane_spec
            __all__ = ["lane_spec"]
        """))
        _write_core_module(tmp_path, """
            from repro.sharding import put_lanes
            x = put_lanes([0.0])
        """, "batched.py")
        fs = self._findings(tmp_path)
        assert len(fs) == 1 and "__all__" in fs[0].message

    def test_bare_device_put_flagged_explicit_clean(self, tmp_path):
        self._tree(tmp_path, """
            import jax
            class Engine:
                def __init__(self, x, sharding):
                    self.a = jax.device_put(x)
                    self.b = jax.device_put(x, sharding)
        """)
        fs = self._findings(tmp_path)
        assert len(fs) == 1 and "bare jax.device_put" in fs[0].message

    def test_donated_self_attr_without_rebind_flagged(self, tmp_path):
        # the cross-module donation hole CAS003 cannot see: the
        # donate_argnums annotation lives in sharding/specs.py while the
        # stale self._cache read-after-donation sits in core/
        self._tree(tmp_path, """
            from repro.sharding import jit_scatter
            class Engine:
                def __init__(self, fn):
                    self._scatter = jit_scatter(fn)
                def step(self):
                    out = self._scatter(self._cache)
                    return out
        """)
        fs = self._findings(tmp_path)
        assert len(fs) == 1 and "donated position 0" in fs[0].message
        assert "_cache" in fs[0].message

    def test_donated_state_read_before_rebind_flagged(self, tmp_path):
        # the tick's update program donates the learned state too: a
        # caller that reads self._state after the call, before rebinding
        # it from the outputs, reads a dead buffer
        self._tree(tmp_path, """
            from repro.sharding import jit_update
            class Engine:
                def __init__(self, fn):
                    self._update = jit_update(fn)
                def commit(self):
                    cache, state = self._update(self._cache, self._state)
                    stale = self._state
                    self._cache, self._state = cache, state
                    return stale
                def commit_clean(self):
                    cache, state = self._update(self._cache, self._state)
                    self._cache, self._state = cache, state
                    return self._state
        """)
        fs = self._findings(tmp_path)
        assert len(fs) == 1 and "read after" in fs[0].message
        assert "self._state" in fs[0].message

    def test_real_core_tree_conforms(self):
        res = run_analysis(REPO_ROOT, paths=["src"],
                           rules=[ShardingContractRule()])
        assert res.findings == [], "\n".join(
            f.render() for f in res.findings)


# ---------------------------------------------------------------------------
# engine: suppressions, baseline, CLI
# ---------------------------------------------------------------------------
class TestEngine:
    def test_same_line_suppression(self, tmp_path):
        (tmp_path / "examples").mkdir()
        (tmp_path / "examples/e.py").write_text(
            "import numpy as np\n"
            "r = np.random.default_rng()"
            "  # cascade-lint: disable=CAS001 demo entropy source\n")
        res = run_analysis(tmp_path, paths=["examples"],
                           rules=[RngDisciplineRule()])
        assert res.findings == [] and res.suppressed == 1

    def test_next_line_and_file_suppression(self, tmp_path):
        (tmp_path / "examples").mkdir()
        (tmp_path / "examples/a.py").write_text(
            "import numpy as np\n"
            "# cascade-lint: disable-next-line=CAS001 demo entropy\n"
            "r = np.random.default_rng()\n")
        (tmp_path / "examples/b.py").write_text(
            "# cascade-lint: disable-file=CAS001 demo entropy\n"
            "import numpy as np\n"
            "r = np.random.default_rng()\n"
            "q = np.random.default_rng()\n")
        res = run_analysis(tmp_path, paths=["examples"],
                           rules=[RngDisciplineRule()])
        assert res.findings == [] and res.suppressed == 3

    def test_wrong_id_not_suppressed(self, tmp_path):
        (tmp_path / "examples").mkdir()
        (tmp_path / "examples/e.py").write_text(
            "import numpy as np\n"
            "r = np.random.default_rng()"
            "  # cascade-lint: disable=CAS002 wrong rule on purpose\n")
        res = run_analysis(tmp_path, paths=["examples"],
                           rules=[RngDisciplineRule()])
        assert len(res.findings) == 1

    def test_baseline_roundtrip_ignores_line_moves(self, tmp_path):
        (tmp_path / "examples").mkdir()
        src = tmp_path / "examples/e.py"
        src.write_text("import numpy as np\n"
                       "r = np.random.default_rng()\n")
        res = run_analysis(tmp_path, paths=["examples"],
                           rules=[RngDisciplineRule()])
        bl = tmp_path / "analysis-baseline.txt"
        bl.write_text(render_baseline(res.findings))
        prints = load_baseline(bl)
        assert len(prints) == 1
        # move the finding two lines down: fingerprint must not change
        src.write_text("import numpy as np\n\n\n"
                       "r = np.random.default_rng()\n")
        res2 = run_analysis(tmp_path, paths=["examples"],
                            rules=[RngDisciplineRule()])
        assert {fingerprint(f) for f in res2.findings} == prints

    def test_cli_strict_exit_codes(self, tmp_path, capsys):
        (tmp_path / "pyproject.toml").write_text("[project]\n")
        (tmp_path / "src").mkdir()
        (tmp_path / "src/clean.py").write_text("x = 1\n")
        assert main(["--root", str(tmp_path), "--strict", "src"]) == 0
        (tmp_path / "src/dirty.py").write_text(
            "import numpy as np\nr = np.random.default_rng()\n")
        assert main(["--root", str(tmp_path), "--strict", "src"]) == 1
        capsys.readouterr()

    def test_cli_baseline_gates_old_but_not_new(self, tmp_path, capsys):
        (tmp_path / "pyproject.toml").write_text("[project]\n")
        (tmp_path / "src").mkdir()
        (tmp_path / "src/old.py").write_text(
            "import numpy as np\nr = np.random.default_rng()\n")
        assert main(["--root", str(tmp_path), "--write-baseline",
                     "src"]) == 0
        assert main(["--root", str(tmp_path), "--strict", "src"]) == 0
        (tmp_path / "src/new.py").write_text(
            "import numpy as np\nq = np.random.default_rng()\n")
        assert main(["--root", str(tmp_path), "--strict", "src"]) == 1
        out = capsys.readouterr().out
        assert "[baselined]" in out

    def test_cli_list_rules(self, capsys):
        assert main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for cls in ALL_RULES:
            assert cls.id in out

    def test_find_repo_root(self):
        assert find_repo_root(Path(__file__).parent) == REPO_ROOT

    def test_syntax_error_reported_as_cas000(self, tmp_path):
        (tmp_path / "examples").mkdir()
        (tmp_path / "examples/broken.py").write_text("def f(:\n")
        res = run_analysis(tmp_path, paths=["examples"],
                           rules=[RngDisciplineRule()])
        assert len(res.findings) == 1 and res.findings[0].rule == "CAS000"


# ---------------------------------------------------------------------------
# suppression-justification policy + --format github
# ---------------------------------------------------------------------------
class TestSuppressionPolicy:
    def test_bare_suppression_still_suppresses_but_is_flagged(
            self, tmp_path):
        (tmp_path / "examples").mkdir()
        (tmp_path / "examples/e.py").write_text(
            "import numpy as np\n"
            "r = np.random.default_rng()"
            "  # cascade-lint: disable=CAS001\n")
        res = run_analysis(tmp_path, paths=["examples"],
                           rules=[RngDisciplineRule()])
        # the waiver the author intended stays effective ...
        assert res.suppressed == 1
        # ... but the missing "why" is a CAS000 finding of its own
        assert len(res.findings) == 1
        assert res.findings[0].rule == "CAS000"
        assert "no justification" in res.findings[0].message
        assert res.findings[0].line == 2

    def test_justified_suppression_is_clean(self, tmp_path):
        (tmp_path / "examples").mkdir()
        (tmp_path / "examples/e.py").write_text(
            "import numpy as np\n"
            "r = np.random.default_rng()"
            "  # cascade-lint: disable=CAS001 -- demo entropy, not "
            "engine state\n")
        res = run_analysis(tmp_path, paths=["examples"],
                           rules=[RngDisciplineRule()])
        assert res.findings == [] and res.suppressed == 1

    def test_justification_policy_is_not_waivable(self, tmp_path):
        # a disable-file=CAS000 cannot hide the bare-suppression report:
        # the policy findings are appended after the suppression filter
        (tmp_path / "examples").mkdir()
        (tmp_path / "examples/e.py").write_text(
            "# cascade-lint: disable-file=CAS000 trying to hide\n"
            "import numpy as np\n"
            "r = np.random.default_rng()"
            "  # cascade-lint: disable=CAS001\n")
        res = run_analysis(tmp_path, paths=["examples"],
                           rules=[RngDisciplineRule()])
        assert any(f.rule == "CAS000" and "no justification" in f.message
                   for f in res.findings)


class TestGithubFormat:
    def _dirty_tree(self, tmp_path):
        (tmp_path / "pyproject.toml").write_text("[project]\n")
        (tmp_path / "src").mkdir()
        (tmp_path / "src/dirty.py").write_text(
            "import numpy as np\nr = np.random.default_rng()\n")

    def test_cli_emits_workflow_commands(self, tmp_path, capsys):
        self._dirty_tree(tmp_path)
        main(["--root", str(tmp_path), "--format", "github", "src"])
        out = capsys.readouterr().out
        assert "::error file=src/dirty.py,line=2," in out
        assert "title=CAS001::" in out
        assert "cascade-lint: 1 finding(s)" in out

    def test_baselined_findings_annotate_as_notices(self, tmp_path,
                                                    capsys):
        self._dirty_tree(tmp_path)
        assert main(["--root", str(tmp_path), "--write-baseline",
                     "src"]) == 0
        capsys.readouterr()
        main(["--root", str(tmp_path), "--format", "github", "src"])
        out = capsys.readouterr().out
        assert "::notice file=src/dirty.py" in out
        assert "title=CAS001 [baselined]::" in out

    def test_message_escaping(self):
        f = Finding("CAS999", "a.py", 3, 0, "50% of\nlines")
        line = _render_github(f)
        assert "%25" in line and "%0A" in line
        assert "\n" not in line

    def test_json_alias_still_works(self, tmp_path, capsys):
        self._dirty_tree(tmp_path)
        main(["--root", str(tmp_path), "--json", "src"])
        out = capsys.readouterr().out
        assert out.lstrip().startswith("[") and '"CAS001"' in out


# ---------------------------------------------------------------------------
# the tree itself
# ---------------------------------------------------------------------------
class TestTreeIsClean:
    def test_run_analysis_clean_on_repo(self):
        res = run_analysis(REPO_ROOT)
        baseline = load_baseline(REPO_ROOT / "analysis-baseline.txt")
        fresh = [f for f in res.findings if fingerprint(f) not in baseline]
        assert fresh == [], "\n".join(f.render() for f in fresh)

    def test_committed_baseline_is_empty(self):
        # satellite contract: violations are FIXED, not waived
        assert load_baseline(REPO_ROOT / "analysis-baseline.txt") == set()

    def test_cli_strict_exits_zero(self):
        proc = subprocess.run(
            [sys.executable, "-m", "repro.analysis", "--strict"],
            cwd=REPO_ROOT, capture_output=True, text=True,
            env={"PYTHONPATH": "src", "PATH": "/usr/local/bin:/usr/bin:/bin"})
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_regression_salted_hash_in_streams_is_caught(self):
        # re-introduce the PR-1 bug into the real module source: seed
        # derived via builtin hash() instead of zlib.crc32
        src = (REPO_ROOT / "src/repro/data/streams.py").read_text()
        broken = src.replace('zlib.crc32(f"{seed}:{name}".encode())',
                             'hash(f"{seed}:{name}")')
        assert broken != src, "streams.py seeding changed upstream"
        fs = run_rule(DeterminismRule(), broken, "src/repro/data/streams.py")
        assert any(f.rule == "CAS002" and "salted" in f.message
                   for f in fs)


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
