"""Data pipeline determinism/resume; optimizer behaviour; grad compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import FileBackedTokens, SyntheticTokens
from repro.optim import grad_compress
from repro.optim.adamw import AdamW, apply_updates, global_norm
from repro.optim.schedules import warmup_cosine
from repro.storage.fsapi import TierFS
from repro.storage.tiers import DRAM, Tier


def test_pipeline_deterministic_and_resumable():
    p1 = SyntheticTokens(1000, 2, 16, seed=5)
    a = [p1.next()["tokens"] for _ in range(4)]
    p2 = SyntheticTokens(1000, 2, 16, seed=5)
    for _ in range(2):
        p2.next()
    state = p2.state()
    p3 = SyntheticTokens(1000, 2, 16, seed=5)
    p3.load_state(state)
    np.testing.assert_array_equal(a[2], p3.next()["tokens"])


def test_pipeline_state_through_fs():
    fs = TierFS(Tier(DRAM))
    p = SyntheticTokens(1000, 2, 16, seed=1)
    p.next(); p.next()
    p.save_state(fs)
    q = SyntheticTokens(1000, 2, 16, seed=1)
    assert q.restore_state(fs)
    np.testing.assert_array_equal(p.next()["tokens"], q.next()["tokens"])


def test_pipeline_state_missing_reads_as_fresh():
    assert not SyntheticTokens(1000, 2, 16, seed=1).restore_state(TierFS(Tier(DRAM)))


@pytest.mark.parametrize("blob", [b"{\"seed\": 1, \"st", b"{\"seed\": 2, \"step\": 3}"],
                         ids=["corrupt", "other_seed"])
def test_pipeline_state_bad_file_raises(blob):
    """A resumed model must not silently get the corpus from step 0."""
    fs = TierFS(Tier(DRAM))
    fd = fs.open("/datapipe.json")
    fs.pwrite(fd, blob.ljust(256), 0)
    fs.close(fd)
    with pytest.raises(ValueError):
        SyntheticTokens(1000, 2, 16, seed=1).restore_state(fs)


def test_file_backed_tokens():
    fs = TierFS(Tier(DRAM))
    tok = np.arange(100, dtype=np.int32)
    FileBackedTokens.write_shard(fs, "/shard0", tok[:60])
    FileBackedTokens.write_shard(fs, "/shard1", tok[60:])
    p = FileBackedTokens(fs, ["/shard0", "/shard1"], batch=2, seq=8)
    b = p.next()["tokens"]
    assert b.shape == (2, 8)
    assert set(b.reshape(-1)).issubset(set(tok.tolist()))


def test_adamw_converges_on_quadratic():
    opt = AdamW(lr=0.1, weight_decay=0.0, clip_norm=None)
    params = {"x": jnp.array([5.0, -3.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = {"x": 2 * params["x"]}
        upd, state, _ = opt.update(grads, state, params)
        params = apply_updates(params, upd)
    assert float(jnp.abs(params["x"]).max()) < 1e-2


def test_grad_clip_and_norm():
    opt = AdamW(lr=0.1, clip_norm=1.0)
    params = {"x": jnp.zeros(3)}
    state = opt.init(params)
    upd, state, m = opt.update({"x": jnp.full(3, 100.0)}, state, params)
    assert float(m["grad_norm"]) > 100
    assert float(global_norm({"x": jnp.full(3, 100.0)})) == float(m["grad_norm"])


def test_schedule_shapes():
    f = warmup_cosine(10, 100)
    assert float(f(jnp.int32(0))) == 0.0
    assert abs(float(f(jnp.int32(10))) - 1.0) < 1e-6
    assert float(f(jnp.int32(100))) <= 0.11


def test_grad_compress_bounded_error():
    g = {"a": jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 3,
         "b": jax.random.normal(jax.random.PRNGKey(1), (7, 13))}
    gc = grad_compress.compress_tree(g)
    for k in g:
        scale = jnp.abs(g[k]).max() / 127
        assert float(jnp.abs(gc[k] - g[k]).max()) <= float(scale) * 1.01 + 1e-6
