"""Multi-device behaviors in a SUBPROCESS (host-device count is locked at
first jax init, so these cannot run in the main pytest process):

* tiny-config lower+compile on a (4, 4) mesh for train/prefill/decode,
  including the shard_map MoE expert-parallel path,
* EP MoE output == single-device oracle,
* elastic checkpoint restore across different mesh shapes,
* int8 compressed all-reduce under shard_map on a pod axis.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_smoke
    from repro.models import api, SHAPES
    from repro.models.config import ShapeCell
    from repro.sharding import use_mesh
    from repro.launch.dryrun import build_step
    from repro.launch.mesh import make_mesh
    from repro.launch.roofline import collective_bytes

    out = {}
    mesh = make_mesh((4, 4), ("data", "model"))

    # 1) lower + compile tiny cells on the mesh (dense + moe + ssm)
    for arch in ("granite-3-8b", "qwen3-moe-30b-a3b", "zamba2-1.2b"):
        cfg = get_smoke(arch)
        for kind, cell in (("train", ShapeCell("t", 64, 8, "train")),
                           ("decode", ShapeCell("d", 64, 8, "decode"))):
            with use_mesh(mesh):
                fn, args = build_step(cfg, cell, mesh)
                compiled = fn.lower(*args).compile()
                txt = compiled.as_text()
            cb = collective_bytes(txt)
            out[f"{arch}:{kind}:collective_bytes"] = cb.get("total", 0.0)

    # 2) EP MoE == local oracle
    from repro.models import moe
    from repro.models.common import init_params
    cfg = get_smoke("qwen3-moe-30b-a3b")
    p = init_params(moe.moe_schema(cfg, 0), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                          jnp.float32).astype(jnp.bfloat16)
    y_local, aux_local = moe.moe_apply(cfg, p, x)      # no mesh -> oracle
    with use_mesh(mesh):
        y_ep, aux_ep = jax.jit(lambda pp, xx: moe.moe_apply(cfg, pp, xx))(p, x)
    d = float(jnp.max(jnp.abs(y_local.astype(jnp.float32)
                              - y_ep.astype(jnp.float32))))
    out["moe_ep_vs_local_maxdiff"] = d
    out["moe_aux_diff"] = abs(float(aux_local) - float(aux_ep))

    # 3) elastic restore across meshes
    from repro.ckpt import save_pytree, load_pytree
    from repro.sharding import named_sharding
    import tempfile
    cfg = get_smoke("granite-3-8b")
    params = api.init(cfg, jax.random.PRNGKey(0))
    tmp = tempfile.mkdtemp()
    save_pytree({"params": params}, tmp, 1)
    mesh2 = make_mesh((2, 2), ("data", "model"))
    with use_mesh(mesh2):
        shardings = jax.tree.map(
            lambda sp: NamedSharding(mesh2, sp), api.pspecs(cfg, mesh2),
            is_leaf=lambda z: type(z).__name__ == "PartitionSpec")
        tree, _ = load_pytree(tmp, shardings={"params": shardings})
    ok = all(np.array_equal(np.asarray(a), np.asarray(b))
             for a, b in zip(jax.tree.leaves(params),
                             jax.tree.leaves(tree["params"])))
    out["elastic_restore_exact"] = bool(ok)

    # 4) compressed all-reduce mean over a pod axis
    from repro.train.compress import compressed_allreduce_mean
    from jax.experimental.shard_map import shard_map
    pmesh = make_mesh((4, 4), ("pod", "data"))
    g = jax.random.normal(jax.random.PRNGKey(2), (4, 128), jnp.float32)
    want = g.mean(axis=0, keepdims=True)
    got = shard_map(lambda x: compressed_allreduce_mean(x, "pod"),
                    mesh=pmesh, in_specs=P("pod", None),
                    out_specs=P("pod", None), check_rep=False)(g)
    err = float(jnp.max(jnp.abs(got - jnp.broadcast_to(want, got.shape))))
    scale = float(jnp.max(jnp.abs(g))) / 127.0
    out["compressed_ar_err"] = err
    out["compressed_ar_bound"] = scale
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def subproc_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, proc.stdout[-2000:]
    return json.loads(line[-1][len("RESULT "):])


def test_mesh_cells_compile_and_emit_collectives(subproc_results):
    r = subproc_results
    for arch in ("granite-3-8b", "qwen3-moe-30b-a3b", "zamba2-1.2b"):
        assert r[f"{arch}:train:collective_bytes"] > 0, arch
        assert f"{arch}:decode:collective_bytes" in r


def test_moe_ep_matches_local_oracle(subproc_results):
    assert subproc_results["moe_ep_vs_local_maxdiff"] < 0.15
    assert subproc_results["moe_aux_diff"] < 1e-5


def test_elastic_restore(subproc_results):
    assert subproc_results["elastic_restore_exact"] is True


def test_compressed_allreduce_error_bounded(subproc_results):
    r = subproc_results
    assert r["compressed_ar_err"] <= r["compressed_ar_bound"] + 1e-6
