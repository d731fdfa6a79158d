"""Multi-device tests (spawned subprocess with host-platform device count —
the main test process must keep a single device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_debug_mesh
    from repro.search.distributed import make_search_step, distributed_rerank
    from repro.distributed.sharding import param_shardings, use_mesh
    from repro.distributed.elastic import reshard_tree, check_mesh_fits
    from repro.configs.base import get_arch
    from repro.models import model as M

    out = {}
    mesh = make_debug_mesh(4, 2)

    # --- distributed search: sharded scan == exact brute force ---
    rng = np.random.default_rng(0)
    db = rng.standard_normal((512, 32)).astype(np.float32)
    q = rng.standard_normal((3, 32)).astype(np.float32)
    db_j = jax.device_put(jnp.asarray(db), NamedSharding(mesh, P("data", None)))
    step = make_search_step(mesh, k=10, axis="data")
    vals, ids = jax.jit(step)(db_j, jnp.asarray(q))
    ref = q @ db.T
    ref_ids = np.argsort(-ref, axis=1)[:, :10]
    ref_vals = np.take_along_axis(ref, ref_ids, axis=1)
    out["search_ok"] = bool(np.allclose(np.asarray(vals), ref_vals, rtol=1e-5))

    # --- distributed rerank ---
    cand = jnp.asarray(np.sort(rng.choice(512, 64, replace=False)))
    rv, ri = distributed_rerank(mesh, db_j, cand, jnp.asarray(q[0]), 5)
    ref_scores = db[np.asarray(cand)] @ q[0]
    top = np.argsort(-ref_scores)[:5]
    out["rerank_ok"] = bool(np.allclose(np.asarray(rv), ref_scores[top], rtol=1e-5))

    # --- serving: row-sharded column store + batched engine flat scan ---
    from repro.core.types import Query, QueryPlan
    from repro.data.vectors import MultiVectorDatabase
    from repro.serve.engine import BatchEngine

    mdb = MultiVectorDatabase([np.ascontiguousarray(db[:, :16]),
                               np.ascontiguousarray(db[:, 16:])], ["a", "b"])
    eng = BatchEngine(mdb, store=None, mesh=mesh, axis="data")
    queries = [Query(qid=i, vid=(0, 1),
                     vectors={0: q[i, :16], 1: q[i, 16:]}, k=10)
               for i in range(3)]
    pairs = [(qq, QueryPlan(qq.qid, [], [], 0.0, 1.0)) for qq in queries]
    got = eng.search_batch(pairs)
    out["serve_sharded_ok"] = bool(
        all(np.array_equal(np.asarray(got[i]), ref_ids[i]) for i in range(3)))
    out["serve_sharded_dispatches"] = eng.counters.scan

    # --- sharded train step on a reduced arch + elastic reshard ---
    cfg = get_arch("qwen2-7b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    probs = check_mesh_fits(params, mesh)
    out["mesh_fits"] = probs[:3]
    params_sharded = reshard_tree(params, mesh)
    batch = {"tokens": jnp.zeros((8, 32), jnp.int32)}
    with use_mesh(mesh), mesh:
        loss = jax.jit(lambda p, b: M.train_loss(cfg, p, b))(params_sharded, batch)
    out["sharded_loss_finite"] = bool(np.isfinite(float(loss)))

    # reshard to a different mesh shape
    mesh2 = make_debug_mesh(2, 4)
    params2 = reshard_tree(jax.device_get(params_sharded), mesh2)
    with use_mesh(mesh2), mesh2:
        loss2 = jax.jit(lambda p, b: M.train_loss(cfg, p, b))(params2, batch)
    # relative tolerance: different model-axis splits re-block the matmul
    # reductions, so f32 losses drift by reduction order, not by value
    out["elastic_loss_matches"] = bool(
        abs(float(loss) - float(loss2)) < 1e-2 * max(abs(float(loss)), 1.0))

    print("RESULT" + json.dumps(out))
""")


@pytest.mark.slow
def test_multidevice_subprocess():
    proc = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                          text=True, timeout=900,
                          env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                               "HOME": os.environ.get("HOME", "/tmp"),
                               "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    out = json.loads(line[len("RESULT"):])
    assert out["search_ok"]
    assert out["rerank_ok"]
    assert out["serve_sharded_ok"]
    assert out["serve_sharded_dispatches"] == 1  # one group, one dispatch
    assert out["mesh_fits"] == [] or all("%" not in p for p in out["mesh_fits"])
    assert out["sharded_loss_finite"]
    assert out["elastic_loss_matches"]
