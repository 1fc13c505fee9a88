"""Substrate tests: optimizer, schedules, data stream determinism,
checkpoint atomicity/restore/elastic, gradient compression, serve engine."""

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (latest_step, restore_checkpoint,
                              save_checkpoint, save_checkpoint_async)
from repro.data.tokens import TokenStream
from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         compress_local, compression_ratio,
                         cosine_with_warmup)


class TestAdamW:
    def test_converges_quadratic(self):
        params = {"w": jnp.array([5.0, -3.0], jnp.float32)}
        cfg = AdamWConfig(lr=0.1, weight_decay=0.0, clip_norm=0.0)
        state = adamw_init(params)
        for _ in range(200):
            grads = {"w": 2 * params["w"]}
            params, state, m = adamw_update(cfg, params, grads, state)
        assert float(jnp.abs(params["w"]).max()) < 1e-2

    def test_clipping(self):
        params = {"w": jnp.zeros(3)}
        cfg = AdamWConfig(lr=1.0, clip_norm=1.0, weight_decay=0.0)
        state = adamw_init(params)
        _, _, metrics = adamw_update(cfg, params,
                                     {"w": jnp.full(3, 100.0)}, state)
        assert float(metrics["grad_norm"]) > 100

    def test_schedule(self):
        s = cosine_with_warmup(10, 100)
        assert float(s(jnp.asarray(0))) == 0.0
        assert abs(float(s(jnp.asarray(10))) - 1.0) < 1e-5
        assert float(s(jnp.asarray(100))) <= 0.11


class TestTokenStream:
    def test_deterministic_and_resumable(self):
        a = TokenStream(vocab=100, batch=2, seq=16, seed=3)
        batches = [a.next_batch() for _ in range(5)]
        b = TokenStream(vocab=100, batch=2, seq=16, seed=3)
        for _ in range(2):
            b.next_batch()
        st = b.state()
        c = TokenStream(vocab=100, batch=2, seq=16, seed=3)
        c.restore(st)
        for i in range(2, 5):
            nb = c.next_batch()
            np.testing.assert_array_equal(np.asarray(nb["tokens"]),
                                          np.asarray(batches[i]["tokens"]))

    def test_labels_shifted(self):
        s = TokenStream(vocab=50, batch=1, seq=8, seed=0)
        b = s.next_batch()
        np.testing.assert_array_equal(np.asarray(b["labels"][0, :-1]),
                                      np.asarray(b["tokens"][0, 1:]))

    def test_learnable_structure(self):
        """Markov stream: bigram entropy must be far below log(V)."""
        s = TokenStream(vocab=1000, batch=8, seq=256, seed=1)
        toks = np.asarray(s.next_batch()["tokens"]).ravel()
        assert len(np.unique(toks)) < 300  # vocab usage is concentrated


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {"a/b": jnp.arange(6.0).reshape(2, 3),
                "c": jnp.asarray(3, jnp.int32)}
        save_checkpoint(str(tmp_path), 7, tree, metadata={"x": 1})
        out, meta, step = restore_checkpoint(str(tmp_path))
        assert step == 7 and meta == {"x": 1}
        np.testing.assert_array_equal(np.asarray(out["a/b"]),
                                      np.asarray(tree["a/b"]))

    def test_keep_last(self, tmp_path):
        for s in range(5):
            save_checkpoint(str(tmp_path), s, {"x": jnp.zeros(1)},
                            keep_last=2)
        steps = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path))
        assert steps == [3, 4]

    def test_atomic_no_torn_checkpoint(self, tmp_path):
        """A .tmp dir left by a killed writer must be invisible to restore."""
        save_checkpoint(str(tmp_path), 1, {"x": jnp.ones(2)})
        os.makedirs(tmp_path / "step_00000002.tmp")  # simulated torn write
        assert latest_step(str(tmp_path)) == 1
        out, _, step = restore_checkpoint(str(tmp_path))
        assert step == 1

    def test_async(self, tmp_path):
        t = save_checkpoint_async(str(tmp_path), 3, {"x": jnp.ones(4)})
        t.join(timeout=30)
        assert latest_step(str(tmp_path)) == 3

    def test_elastic_reshard(self, tmp_path):
        """Checkpoint written unsharded restores onto a different mesh."""
        import subprocess, sys, textwrap
        code = textwrap.dedent(f"""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.launch.mesh import make_mesh
            from repro.checkpoint import save_checkpoint, restore_checkpoint
            d = r"{tmp_path}"
            tree = {{"w": jnp.arange(64.0).reshape(8, 8)}}
            save_checkpoint(d, 1, tree)
            for shape in [(4, 2), (8, 1), (2, 4)]:
                mesh = make_mesh(shape, ("data", "model"))
                sh = {{"w": NamedSharding(mesh, P("data", "model"))}}
                out, _, _ = restore_checkpoint(d, shardings=sh)
                assert out["w"].sharding.mesh.shape["data"] == shape[0]
                np.testing.assert_array_equal(np.asarray(out["w"]),
                                              np.arange(64.0).reshape(8, 8))
            print("ELASTIC-OK")
        """)
        # JAX_PLATFORMS=cpu: the child must never contend for an
        # accelerator the parent test process may hold.
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.path.abspath(
                       os.path.join(os.path.dirname(__file__), "..", "src")))
        env.pop("XLA_FLAGS", None)
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=300)
        assert "ELASTIC-OK" in r.stdout, r.stderr


class TestCompression:
    def test_error_feedback_reduces_bias(self):
        """With error feedback, the accumulated compressed updates converge
        to the accumulated true gradient (PowerSGD property)."""
        rng = np.random.default_rng(0)
        g_true = jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32))
        err = jnp.zeros((32, 16))
        p_prev = jnp.asarray(rng.normal(size=(32, 4)).astype(np.float32))
        acc = jnp.zeros_like(g_true)
        for _ in range(30):
            p, q, err = compress_local(g_true, err, p_prev)
            acc = acc + p @ q.T
            p_prev = p
        # mean compressed update ~ true gradient
        rel = float(jnp.linalg.norm(acc / 30 - g_true)
                    / jnp.linalg.norm(g_true))
        assert rel < 0.15, rel

    def test_rank_captures_lowrank_exactly(self):
        rng = np.random.default_rng(1)
        u = rng.normal(size=(24, 2)).astype(np.float32)
        v = rng.normal(size=(12, 2)).astype(np.float32)
        g = jnp.asarray(u @ v.T)
        err = jnp.zeros_like(g)
        p_prev = jnp.asarray(rng.normal(size=(24, 4)).astype(np.float32))
        for _ in range(3):
            p, q, err = compress_local(g, err, p_prev)
            p_prev = p
        assert float(jnp.linalg.norm(err) / jnp.linalg.norm(g)) < 1e-3

    def test_ratio(self):
        params = {"w": jnp.zeros((128, 128)), "b": jnp.zeros(128)}
        r = compression_ratio(params, rank=4)
        assert r < 0.1


class TestServeEngine:
    def test_generate_batched(self):
        from repro.configs import get_config
        from repro.models import build_model
        from repro.serve import DecodeEngine, ServeConfig

        cfg = get_config("llama3.2-3b", smoke=True)
        model = build_model(cfg)
        params, _ = model.init(jax.random.PRNGKey(0))
        eng = DecodeEngine(model, params, 2,
                           ServeConfig(max_len=32, max_new_tokens=5))
        prompts = [[1, 2, 3], [4, 5], [6], [7, 8, 9, 10], [11]]
        outs = eng.generate(prompts)
        assert len(outs) == 5
        assert all(len(o) == 5 for o in outs)
        assert all(0 <= t < cfg.vocab for o in outs for t in o)

    def test_greedy_deterministic(self):
        from repro.configs import get_config
        from repro.models import build_model
        from repro.serve import DecodeEngine, ServeConfig

        cfg = get_config("qwen3-32b", smoke=True)
        model = build_model(cfg)
        params, _ = model.init(jax.random.PRNGKey(1))
        eng = DecodeEngine(model, params, 2,
                           ServeConfig(max_len=24, max_new_tokens=4))
        a = eng.generate([[1, 2], [3]])
        b = eng.generate([[1, 2], [3]])
        assert a == b
