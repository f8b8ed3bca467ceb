"""Sharding rules + a reduced-mesh dry-run executed in a subprocess (so the
512-device XLA flag never leaks into this test process)."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro import sharding as shd
from repro.configs import get_smoke_config
from repro.models import transformer as tf
from repro.sharding import param_pspecs


def test_param_pspec_rules():
    cfg = get_smoke_config("mixtral-8x22b")
    params = jax.eval_shape(
        lambda k: tf.init_params(k, cfg), jax.random.PRNGKey(0))
    specs = param_pspecs(params)
    flat = {jax.tree_util.keystr(p): s for p, s in
            jax.tree_util.tree_flatten_with_path(specs)[0]}
    emb = [v for k, v in flat.items() if "embed" in k and "table" in k][0]
    # vocab rows sharded over model (padded_vocab guarantees divisibility)
    assert emb == P("model", None)
    wq = [v for k, v in flat.items() if "attn" in k and "wq" in k][0]
    assert wq == P(None, None, "model")        # stacked: leading periods dim
    w_in = [v for k, v in flat.items() if "moe" in k and "'w_in'" in k][0]
    assert w_in == P(None, None, None, "model")  # tensor mode: ff sharded
    router = [v for k, v in flat.items() if "router" in k][0]
    assert all(a is None for a in router)


def test_param_pspec_expert_mode():
    cfg = get_smoke_config("dbrx-132b")
    params = jax.eval_shape(
        lambda k: tf.init_params(k, cfg), jax.random.PRNGKey(0))
    specs = param_pspecs(params, moe_mode="expert")
    flat = {jax.tree_util.keystr(p): s for p, s in
            jax.tree_util.tree_flatten_with_path(specs)[0]}
    w_in = [v for k, v in flat.items() if "moe" in k and "'w_in'" in k][0]
    assert w_in == P(None, "model", None, None)  # expert dim sharded


def test_constrain_is_noop_without_mesh():
    shd.set_mesh(None)
    x = jax.numpy.ones((4, 4))
    y = shd.constrain(x, ("data", None))
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_constrain_skips_indivisible_dims():
    devs = np.array(jax.devices()).reshape(1, -1)
    mesh = Mesh(devs, ("data", "model"))
    shd.set_mesh(mesh)
    try:
        x = jax.numpy.ones((3, 4))       # 3 not divisible by any axis > 1
        y = jax.jit(lambda a: shd.constrain(a, ("model", None)))(x)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    finally:
        shd.set_mesh(None)


DRYRUN_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {src!r})
import jax, functools, json
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.models import transformer as tf
from repro import sharding as shd
from repro.launch.mesh import make_mesh
from repro.sharding import param_pspecs

cfg = get_smoke_config({arch!r})
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
shd.set_mesh(mesh)
params = jax.eval_shape(functools.partial(tf.init_params, cfg=cfg),
                        jax.random.PRNGKey(0))
pspecs = param_pspecs(params)
ns = shd.tree_named_shardings(mesh, pspecs)
batch = {{
    "tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
    "targets": jax.ShapeDtypeStruct((8, 64), jnp.int32),
}}
if cfg.encoder is not None:
    batch["frames"] = jax.ShapeDtypeStruct((8, 64, cfg.d_model),
                                           cfg.jnp_dtype)
if cfg.vision_stub:
    batch["image_embeds"] = jax.ShapeDtypeStruct(
        (8, cfg.n_image_tokens, cfg.d_model), cfg.jnp_dtype)
bns = jax.tree.map(lambda l: NamedSharding(
    mesh, P(("pod", "data")) if l.shape[0] == 8 else P()), batch)

def step(params, batch):
    loss, m = tf.train_loss(params, batch, cfg, remat=False)
    return loss

with mesh:
    compiled = jax.jit(step, in_shardings=(ns, bns)).lower(
        params, batch).compile()
cost = compiled.cost_analysis()
if isinstance(cost, (list, tuple)):
    cost = cost[0]
print(json.dumps({{"flops": float(cost.get("flops", 0.0))}}))
"""


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "mixtral-8x22b",
                                  "jamba-1.5-large-398b",
                                  "seamless-m4t-medium"])
def test_reduced_mesh_multipod_lowering(arch):
    """(pod, data, model) = (2, 2, 2) mesh lower+compile in a subprocess."""
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    code = DRYRUN_SNIPPET.format(src=src, arch=arch)
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["flops"] > 0
