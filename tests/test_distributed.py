"""Distributed-path tests: run in a subprocess with 8 forced host devices so
the main pytest process keeps seeing 1 device."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

pytestmark = pytest.mark.slow  # subprocess-per-test with 8 forced devices


def run_with_devices(code: str, n: int = 8, timeout: int = 420):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n}",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_compressed_psum_matches_psum():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import compressed_psum_local
        mesh = jax.make_mesh((8,), ("pod",))
        x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8) / 7.0
        def local(v):
            return compressed_psum_local(v, "pod")
        fn = jax.shard_map(local, mesh=mesh, in_specs=(P(),), out_specs=P(),
                           check_vma=False)
        got = fn(x)
        want = x * 8  # psum of identical replicas
        rel = float(jnp.abs(got - want).max() / jnp.abs(want).max())
        assert rel < 2e-2, rel   # int8 quantization error bound
        print("compressed_psum ok", rel)
    """)


def test_compressed_psum_reduces_allreduce_bytes():
    run_with_devices("""
        import jax, jax.numpy as jnp, re
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.distributed.compression import compressed_psum_local
        mesh = jax.make_mesh((8,), ("pod",))
        x = jnp.zeros((1024, 64), jnp.float32)
        sh = NamedSharding(mesh, P())
        plain = jax.jit(
            jax.shard_map(lambda v: jax.lax.psum(v, "pod"), mesh=mesh,
                          in_specs=(P(),), out_specs=P(), check_vma=False),
            in_shardings=(sh,)).lower(x).compile().as_text()
        comp = jax.jit(
            jax.shard_map(lambda v: compressed_psum_local(v, "pod"),
                          mesh=mesh, in_specs=(P(),), out_specs=P(),
                          check_vma=False),
            in_shardings=(sh,)).lower(x).compile().as_text()
        def coll_bytes(txt):
            tot = 0
            for line in txt.splitlines():
                if re.search(r"= \\S+ (all-gather|all-reduce|reduce-scatter)", line) or (
                        " = " in line and re.search(r"(all-gather|all-reduce|reduce-scatter)\\(", line)):
                    m = re.search(r"= (\\w+)\\[([\\d,]*)\\]", line)
                    if m:
                        dt, dims = m.groups()
                        n = 1
                        for d in dims.split(","):
                            if d: n *= int(d)
                        tot += n * {"f32":4,"bf16":2,"s8":1,"u8":1}.get(dt, 4)
            return tot
        b_plain, b_comp = coll_bytes(plain), coll_bytes(comp)
        print("plain", b_plain, "compressed", b_comp)
        assert b_comp < b_plain, (b_plain, b_comp)
    """)


def test_distributed_sample_greedy_matches_argmax():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.sharding import ParallelContext
        from repro.serving.sampler import SamplerConfig, distributed_sample
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        par = ParallelContext(mesh=mesh)
        logits = jax.random.normal(jax.random.key(0), (4, 64))
        tok = distributed_sample(logits, jax.random.key(1),
                                 SamplerConfig(greedy=True), par)
        np.testing.assert_array_equal(np.asarray(tok),
                                      np.asarray(jnp.argmax(logits, -1)))
        # stochastic: valid token ids
        tok2 = distributed_sample(logits, jax.random.key(2),
                                  SamplerConfig(temperature=1.0), par)
        assert ((np.asarray(tok2) >= 0) & (np.asarray(tok2) < 64)).all()
        print("distributed_sample ok")
    """)


def test_sharded_train_step_matches_single_device():
    run_with_devices("""
        import jax, jax.numpy as jnp
        from repro.configs.base import ModelConfig
        from repro.distributed.sharding import ParallelContext, param_shardings
        from repro.models import api
        from repro.train.loop import make_train_step
        from repro.train.optimizer import AdamWConfig, init_opt_state
        from repro.launch.mesh import make_host_mesh

        cfg = ModelConfig(name="t", n_layers=2, d_model=64, n_heads=4,
                          n_kv_heads=2, d_ff=128, vocab_size=320,
                          dtype="float32", param_dtype="float32", remat="none")
        m = api.get_model(cfg)
        p = m.init_params(jax.random.key(0), cfg)
        toks = jax.random.randint(jax.random.key(1), (8, 32), 0, 320)
        batch = (toks, jnp.roll(toks, -1, 1), jnp.ones((8, 32), jnp.float32))
        oc = AdamWConfig(lr=1e-3)
        p_ref, _, met_ref = jax.jit(make_train_step(cfg, oc, None))(
            p, init_opt_state(p), batch)

        mesh = make_host_mesh(4, 2)
        par = ParallelContext(mesh=mesh)
        sh = param_shardings(p, par)
        p_sh = jax.device_put(p, sh)
        step = jax.jit(make_train_step(cfg, oc, par))
        p_out, _, met = step(p_sh, init_opt_state(p_sh), batch)
        d = jax.tree.map(lambda a, b: float(jnp.abs(a - jax.device_get(b)).max()),
                         p_ref, p_out)
        mx = max(jax.tree.leaves(d))
        assert mx < 1e-4, mx
        assert abs(float(met["loss"]) - float(met_ref["loss"])) < 1e-4
        print("sharded train step ok", mx)
    """)


def test_seq_parallel_decode_matches_dense():
    run_with_devices("""
        import jax, jax.numpy as jnp, dataclasses
        from repro.configs.base import ModelConfig
        from repro.distributed.sharding import ParallelContext
        from repro.models import api
        from repro.launch.mesh import make_host_mesh

        cfg = ModelConfig(name="t", n_layers=2, d_model=64, n_heads=4,
                          n_kv_heads=2, d_ff=128, vocab_size=320,
                          dtype="float32", param_dtype="float32")
        m = api.get_model(cfg)
        p = m.init_params(jax.random.key(0), cfg)
        toks = jax.random.randint(jax.random.key(1), (2, 12), 3, 300)
        logits_full, _, _ = m.forward(p, toks, cfg)
        _, cache = m.prefill(p, toks[:, :11], cfg, max_len=16)
        lg_ref, _ = m.decode_step(p, toks[:, 11:12], cache,
                                  jnp.full((2,), 12, jnp.int32), cfg)

        mesh = make_host_mesh(8, 1)
        par = ParallelContext(mesh=mesh, kv_seq_axis="data", fsdp=False)
        lg_sp, _ = m.decode_step(p, toks[:, 11:12], cache,
                                 jnp.full((2,), 12, jnp.int32), cfg, par)
        err = float(jnp.abs(lg_sp - lg_ref).max())
        assert err < 1e-3, err
        print("seq-parallel decode ok", err)
    """)


def test_elastic_restore_across_meshes(tmp_path):
    run_with_devices(f"""
        import jax, jax.numpy as jnp
        from repro.checkpoint.checkpointer import Checkpointer
        from repro.distributed.elastic import elastic_restore
        from repro.distributed.sharding import ParallelContext
        from repro.launch.mesh import make_host_mesh
        from repro.configs.base import ModelConfig
        from repro.models import api

        cfg = ModelConfig(name="t", n_layers=2, d_model=64, n_heads=4,
                          n_kv_heads=2, d_ff=128, vocab_size=320,
                          dtype="float32")
        m = api.get_model(cfg)
        p = m.init_params(jax.random.key(0), cfg)
        ck = Checkpointer(r"{tmp_path}")
        ck.save(p, step=3)
        # restore onto a (2,4) mesh, then onto (8,1) — same values both times
        for shape in [(2, 4), (8, 1)]:
            par = ParallelContext(mesh=make_host_mesh(*shape))
            restored, s = elastic_restore(ck, jax.eval_shape(lambda: p), par)
            ok = jax.tree.map(lambda a, b: bool(jnp.array_equal(
                jax.device_get(a), jax.device_get(b))), p, restored)
            assert all(jax.tree.leaves(ok))
        print("elastic restore ok")
    """)


def test_distributed_greedy_tie_break_matches_argmax():
    """Tied logits across vocab shards: the shard-winner merge must pick
    the LOWEST global index (like unsharded ``jnp.argmax``), not whichever
    shard the pmax reduction visits last."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.sharding import ParallelContext
        from repro.launch.mesh import make_host_mesh
        from repro.serving.sampler import SamplerConfig, distributed_sample

        par = ParallelContext(mesh=make_host_mesh(1, 8))
        V = 64  # 8 tokens per shard
        # ties spanning shards: {7, 23, 55} -> 7, {0, 63} -> 0,
        # {40, 41} (same shard) -> 40
        rows = np.full((3, V), -5.0, np.float32)
        rows[0, [7, 23, 55]] = 2.0
        rows[1, [0, 63]] = 1.0
        rows[2, [40, 41]] = 3.0
        logits = jnp.asarray(rows)
        tok = distributed_sample(logits, jax.random.key(0),
                                 SamplerConfig(greedy=True), par)
        np.testing.assert_array_equal(np.asarray(tok), [7, 0, 40])
        np.testing.assert_array_equal(np.asarray(tok),
                                      np.asarray(jnp.argmax(logits, -1)))
        # gumbel path still returns valid ids under ties
        tok2 = distributed_sample(logits, jax.random.key(1),
                                  SamplerConfig(temperature=1.0), par)
        assert ((np.asarray(tok2) >= 0) & (np.asarray(tok2) < V)).all()
        print("tie-break ok")
    """)
