"""Multi-host (multi-controller) proof — r2 missing #1 / next-#5.

The reference's deployment story is processes-across-machines wired by
IP:port (``/root/reference/run_this.sh:8-17``, ``send_config.py:5-44``). The
TPU-native equivalent is JAX multi-controller SPMD: every host runs the SAME
program, ``jax.distributed.initialize`` forms the cluster, and the global
device list becomes one mesh. These tests run it FOR REAL: two OS processes,
each with 2 virtual CPU devices, joined through a local coordinator — the
same code path a 2-host TPU pod runs, minus the ICI.

Covers: engine construction via ``put_global`` (each process materializes
only its addressable shards — a plain device_put of host numpy fails here),
a 4-stage pipeline decode, and a dp2 x pp2 hybrid, all token-exact vs the
per-process monolithic oracle.
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = textwrap.dedent(
    """
    import os, sys
    pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2"
    )
    sys.path.insert(0, {repo!r})
    import jax
    # must run before ANY backend use (the package import below is safe:
    # POS_SENTINEL is deliberately a numpy scalar — models/cache.py)
    from llm_sharding_tpu.parallel.distributed import initialize_multihost
    initialize_multihost(f"localhost:{{port}}", nproc, pid)
    assert jax.process_count() == nproc, jax.process_count()
    assert jax.device_count() == 2 * nproc

    import numpy as np
    import jax.numpy as jnp
    jax.config.update("jax_default_matmul_precision", "highest")
    from llm_sharding_tpu.models import llama
    from llm_sharding_tpu.models.config import tiny_llama
    from llm_sharding_tpu.parallel.distributed import hybrid_mesh
    from llm_sharding_tpu.parallel.pipeline import pipeline_generate
    from llm_sharding_tpu.parallel.placement import (
        PlacementSpec, stack_stage_params,
    )
    from llm_sharding_tpu.runtime.engine import PipelineEngine
    from llm_sharding_tpu.runtime.generate import generate

    cfg = tiny_llama(num_hidden_layers=8, vocab_size=64)
    # same seed on every host -> identical host-resident weights (the
    # multi-controller convention: every process runs the same program)
    params = llama.init_params(cfg, jax.random.key(0), dtype=jnp.float32)

    # --- 4-stage pipeline across both processes, via the engine ---
    eng = PipelineEngine(cfg, params, num_stages=4, cache_dtype=jnp.float32)
    prompt = np.array([[5, 9, 2, 14]], np.int32)
    res = eng.generate_ids(prompt, 10)
    oracle = generate(cfg, params, prompt, 10, cache_dtype=jnp.float32)
    assert np.array_equal(res.tokens, oracle.tokens), "pipeline mismatch"

    # --- hot repartition still works across hosts ---
    eng.apply_placement(
        PlacementSpec.from_ranges([(0, 3), (3, 4), (4, 6), (6, 8)], 8)
    )
    res2 = eng.generate_ids(prompt, 10)
    assert np.array_equal(res2.tokens, oracle.tokens), "repartition mismatch"

    # --- dp2 x pp2 hybrid: batch rows sharded across processes ---
    mesh = hybrid_mesh(data=2, pipe=2)
    spec = PlacementSpec.balanced(cfg.num_hidden_layers, 2)
    sl, masks = stack_stage_params(spec, params["layers"])
    head = {{k: v for k, v in params.items() if k != "layers"}}
    prompts = np.array([[5, 9, 2, 14], [7, 3, 1, 8]], np.int32)
    from llm_sharding_tpu.parallel.distributed import put_global
    from jax.sharding import NamedSharding, PartitionSpec as P
    from llm_sharding_tpu.parallel.mesh import PIPE_AXIS
    sl = jax.tree.map(
        lambda a: put_global(np.asarray(a), NamedSharding(mesh, P(PIPE_AXIS))),
        sl,
    )
    masks = put_global(np.asarray(masks), NamedSharding(mesh, P(PIPE_AXIS)))
    res3 = pipeline_generate(
        cfg, mesh, sl, masks, head, prompts, 8, cache_dtype=jnp.float32
    )
    want = generate(cfg, params, prompts, 8, cache_dtype=jnp.float32)
    assert np.array_equal(res3.tokens, want.tokens), "hybrid dp x pp mismatch"

    # --- continuous-batching server across both processes ---
    # every process runs the same host loop in lockstep (the multi-controller
    # convention); the serve state takes the put_global assembly path
    eng2 = PipelineEngine(cfg, params, num_stages=4, cache_dtype=jnp.float32)
    srv = eng2.serve(capacity=64)
    pa = np.array([5, 9, 2, 14], np.int32)
    pb = np.array([7, 3, 1], np.int32)
    ra = srv.submit(pa, 8)
    srv.step()
    rb = srv.submit(pb, 6, temperature=0.8, seed=13)  # joins mid-decode
    srv.run_until_idle()
    oa = generate(cfg, params, pa[None], 8, cache_dtype=jnp.float32)
    assert ra.tokens == [
        int(x) for x in oa.tokens[0][len(pa): int(oa.lengths[0])]
    ], "multihost serve greedy mismatch"
    ob = generate(
        cfg, params, pb[None], 6, temperature=0.8, seed=13,
        cache_dtype=jnp.float32,
    )
    assert rb.tokens == [
        int(x) for x in ob.tokens[0][len(pb): int(ob.lengths[0])]
    ], "multihost serve sampled mismatch"

    print(f"MULTIHOST-OK p{{pid}}", flush=True)
    """
).format(repo=REPO)


def _clean_env():
    """Subprocess env: CPU platform, and no inherited XLA_FLAGS (the worker
    script sizes its own virtual device count before importing jax)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def test_two_process_pipeline_token_exact():
    port = _free_port()
    env = _clean_env()
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER, str(pid), "2", str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out, err))
    for pid, (rc, out, err) in enumerate(outs):
        assert rc == 0, f"process {pid} failed:\n{err[-3000:]}"
        assert f"MULTIHOST-OK p{pid}" in out
