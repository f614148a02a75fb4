"""Data pipeline, optimizer, schedule, checkpoint tests."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint import latest_step, restore, save
from repro.configs import get_config
from repro.data import DataConfig, pipeline_stats, sample_batch, stacked_node_batches
from repro.data import pipeline
from repro.optim import adamw, make_optimizer, sgd
from repro.optim.optimizers import apply_updates, clip_by_global_norm, global_norm
from repro.optim.schedules import cosine_decay, inv_sqrt_decay, linear_warmup_cosine


# ------------------------------------------------------------------ data

def test_data_deterministic_and_shard_disjoint():
    cfg = DataConfig(vocab=64, seq_len=16, global_batch=8, n_shards=4, seed=3)
    b1 = sample_batch(cfg, step=5, shard=2)
    b2 = sample_batch(cfg, step=5, shard=2)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
    b3 = sample_batch(cfg, step=5, shard=3)
    assert not np.array_equal(np.asarray(b1["tokens"]), np.asarray(b3["tokens"]))
    b4 = sample_batch(cfg, step=6, shard=2)
    assert not np.array_equal(np.asarray(b1["tokens"]), np.asarray(b4["tokens"]))


def test_data_labels_are_shifted_tokens():
    cfg = DataConfig(vocab=64, seq_len=16, global_batch=4, n_shards=1)
    b = sample_batch(cfg, 0, 0)
    np.testing.assert_array_equal(np.asarray(b["tokens"][:, 1:]), np.asarray(b["labels"][:, :-1]))
    assert b["tokens"].shape == (4, 16)


def test_data_has_learnable_structure():
    """A bigram model on the synthetic stream beats uniform entropy by a wide margin."""
    cfg = DataConfig(vocab=32, seq_len=512, global_batch=8, n_shards=1, seed=0)
    b = sample_batch(cfg, 0, 0)
    toks = np.asarray(b["tokens"]).reshape(-1)
    labs = np.asarray(b["labels"]).reshape(-1)
    counts = np.ones((32, 32))
    for t, l in zip(toks, labs):
        counts[t, l] += 1
    probs = counts / counts.sum(1, keepdims=True)
    nll = -np.mean(np.log(probs[toks, labs]))
    assert nll < 0.8 * np.log(32)


def test_vlm_batch_includes_frontend():
    arch = get_config("internvl2-76b").reduced()
    cfg = DataConfig(vocab=arch.vocab, seq_len=64, global_batch=2, n_shards=1)
    b = sample_batch(cfg, 0, 0, arch)
    assert b["extra_embeds"].shape == (2, arch.frontend.n_tokens, arch.frontend.dim)
    assert b["tokens"].shape == (2, 64 - arch.frontend.n_tokens)


def test_stacked_node_batches():
    cfg = DataConfig(vocab=16, seq_len=8, global_batch=8, n_shards=4)
    sb = stacked_node_batches(cfg, 0)
    assert sb["tokens"].shape == (4, 2, 8)


def test_stacked_node_batches_at_a_published_vocab():
    """mamba2-370m's 50,280 tokens: one call yields every node's batch, equal
    to that node's own ``sample_batch`` (no vocab x vocab table exists)."""
    cfg = DataConfig(vocab=get_config("mamba2-370m").vocab, seq_len=16,
                     global_batch=6, n_shards=3, seed=1)
    sb = stacked_node_batches(cfg, 4)
    assert sb["tokens"].shape == (3, 2, 16)
    assert int(sb["tokens"].max()) < cfg.vocab
    for s in range(3):
        b = sample_batch(cfg, 4, s)
        for k in ("tokens", "labels"):
            np.testing.assert_array_equal(np.asarray(sb[k][s]), np.asarray(b[k]))


def _direct(cfg, step, arch=None):
    """Every shard's batch of ``step`` from the walk of that step alone."""
    return pipeline._node_batches(cfg, arch, jnp.asarray(step, jnp.int32),
                                  jnp.arange(cfg.n_shards, dtype=jnp.int32))


def _assert_batches_equal(got, want):
    assert got.keys() == want.keys()
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]))


@pytest.mark.parametrize("vocab,seq_len,global_batch,n_shards,arch_name,k", [
    (64, 16, 4, 2, None, 16),                         # a small vocab
    ("mamba2-370m", 64, 16, 2, None, 4),              # a published vocab
    ("arch", 64, 2, 1, "internvl2-76b", 8),           # s_text 56: K divides it
    ("arch", 63, 2, 1, "internvl2-76b", 1),           # s_text 55 forces K = 1
])
def test_pipelined_walk_equals_the_direct_one(vocab, seq_len, global_batch, n_shards,
                                              arch_name, k):
    """3K+1 consecutive steps of the pipelined walk give every id (and
    frontend embedding) the step's own direct walk gives, after one cold
    start; at K = 1 no pipeline is kept."""
    arch = get_config(arch_name).reduced() if arch_name else None
    if vocab == "arch":
        vocab = arch.vocab
    elif isinstance(vocab, str):
        vocab = get_config(vocab).vocab
    cfg = DataConfig(vocab=vocab, seq_len=seq_len, global_batch=global_batch,
                     n_shards=n_shards, seed=11)
    assert pipeline_stats(cfg, arch)["k"] == k
    first = 1000
    for t in range(first, first + 3 * k + 1):
        _assert_batches_equal(stacked_node_batches(cfg, t, arch), _direct(cfg, t, arch))
    calls = 0 if k == 1 else 3 * k + 1 + k - 1
    assert pipeline_stats(cfg, arch) == {"segment_calls": calls,
                                         "cold_starts": int(k > 1), "k": k}


def test_pipelined_walk_cold_starts_on_a_jump():
    """A step that does not follow the last one refills the pipeline: a jump
    backwards (a resume) and one forwards each count one cold start, and the
    ids stay the direct walk's."""
    cfg = DataConfig(vocab=96, seq_len=32, global_batch=8, n_shards=4, seed=5)
    k = pipeline_stats(cfg)["k"]
    assert k == 8
    steps = [0, 1, 2, 3, 1, 2, 3, 40, 41]
    for t in steps:
        _assert_batches_equal(stacked_node_batches(cfg, t), _direct(cfg, t))
    assert pipeline_stats(cfg) == {"segment_calls": len(steps) + 3 * (k - 1),
                                   "cold_starts": 3, "k": k}


def test_eval_shape_leaves_the_pipeline_alone():
    """Under ``jax.eval_shape`` the call gives the batch's shapes and dtypes
    and neither counts nor stores anything (no tracer is kept)."""
    cfg = DataConfig(vocab=64, seq_len=16, global_batch=4, n_shards=2, seed=7)
    stacked_node_batches(cfg, 3)
    p = pipeline._PIPELINES[(cfg, None)]
    stats, carry, factors = pipeline_stats(cfg), p.carry, p.factors
    shapes = jax.eval_shape(lambda: stacked_node_batches(cfg, 9))
    assert pipeline_stats(cfg) == stats and p.next_step == 4
    assert p.carry is carry and p.factors is factors
    want = _direct(cfg, 9)
    assert {k: (v.shape, v.dtype) for k, v in shapes.items()} == \
        {k: (v.shape, v.dtype) for k, v in want.items()}
    _assert_batches_equal(stacked_node_batches(cfg, 4), _direct(cfg, 4))
    assert pipeline_stats(cfg)["cold_starts"] == 1


def test_input_programs_keep_their_module_name():
    """The direct and the segment program both lower as ``jit__node_batches``,
    the module name the benchmark's ``input_ms_per_step`` reads."""
    cfg = DataConfig(vocab=64, seq_len=16, global_batch=4, n_shards=2, seed=2)
    stacked_node_batches(cfg, 0)
    p = pipeline._PIPELINES[(cfg, None)]
    step, shards = jnp.int32(1), jnp.arange(2, dtype=jnp.int32)
    for args in ((step, shards), (step, shards, p.factors, p.carry)):
        text = pipeline._node_batches.lower(cfg, None, *args).as_text()
        assert re.search(r"^module @jit__node_batches\b", text, re.M)


@pytest.mark.parametrize("vocab", [17, 50280])
def test_next_token_is_the_categorical_draw(vocab):
    """The walk's draw is ``jax.random.categorical``'s, argmax ties included."""
    e, f = pipeline._markov_factors(DataConfig(vocab=vocab, seq_len=8, global_batch=1))
    tok = jnp.arange(5) * 3 % vocab
    for i in range(4):
        k = jax.random.key(i)
        want = jax.random.categorical(k, e[tok] @ f.T)
        np.testing.assert_array_equal(np.asarray(pipeline._next_token((e, f), tok, k)),
                                      np.asarray(want))
    # ties resolve to the lowest index, as argmax's do
    flat = (jnp.zeros((vocab, 64)), jnp.zeros((vocab, 64)))
    k = jax.random.key(0)
    np.testing.assert_array_equal(
        np.asarray(pipeline._next_token(flat, tok, k)),
        np.asarray(jax.random.categorical(k, jnp.zeros((5, vocab)))))


@pytest.mark.parametrize("env", [None, "elsewhere"])
def test_compile_cache_placed_from_outside(monkeypatch, tmp_path, env):
    """JAX_COMPILATION_CACHE_DIR wins and no directory is set in code;
    otherwise the cache is the checkout's fixed .jax_cache/."""
    from repro.launch import train

    before = jax.config.jax_compilation_cache_dir
    if env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = train.use_compile_cache()
        if env:
            assert got == str(tmp_path / env)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert got == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ------------------------------------------------------------------ optim

def _quad_problem():
    A = jnp.diag(jnp.array([1.0, 10.0, 0.1]))
    x0 = jnp.array([5.0, -3.0, 8.0])
    f = lambda x: 0.5 * x @ A @ x
    return f, x0


@pytest.mark.parametrize("opt,lr", [(sgd(), 0.15), (sgd(momentum=0.9), 0.02),
                                    (adamw(weight_decay=0.0), 0.3)])
def test_optimizers_minimize_quadratic(opt, lr):
    f, x = _quad_problem()
    state = opt.init(x)
    for _ in range(600):
        g = jax.grad(f)(x)
        upd, state = opt.update(g, state, x, jnp.float32(lr))
        x = apply_updates(x, upd)
    assert float(f(x)) < 1e-3


def test_adamw_weight_decay_shrinks_params():
    opt = adamw(weight_decay=0.5)
    x = jnp.ones(4)
    state = opt.init(x)
    upd, _ = opt.update(jnp.zeros(4), state, x, jnp.float32(0.1))
    assert float(jnp.max(apply_updates(x, upd))) < 1.0


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, n = clip_by_global_norm(g, 1.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)
    assert float(n) == pytest.approx(20.0, rel=1e-5)


@settings(max_examples=20, deadline=None)
@given(step=st.integers(0, 10_000))
def test_schedules_positive_and_bounded(step):
    s = jnp.asarray(step)
    for sched in [cosine_decay(1e-3, 5000), linear_warmup_cosine(1e-3, 100, 5000),
                  inv_sqrt_decay(1e-3, 100)]:
        v = float(sched(s))
        assert 0 <= v <= 1e-3 + 1e-9


def test_warmup_ramps_up():
    sched = linear_warmup_cosine(1.0, 100, 1000)
    assert float(sched(jnp.asarray(10))) < float(sched(jnp.asarray(99)))
    assert float(sched(jnp.asarray(100))) == pytest.approx(1.0, rel=1e-3)


# ------------------------------------------------------------------ checkpoint

def test_checkpoint_roundtrip(tmp_path):
    tree = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nested": {"b": jnp.ones(4, jnp.bfloat16)},
            "stack": [jnp.zeros(2), jnp.ones(3)]}
    save(str(tmp_path), 7, tree, metadata={"loss": 1.5})
    assert latest_step(str(tmp_path)) == 7
    like = jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)
    out, manifest = restore(str(tmp_path), tree)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
    assert out["nested"]["b"].dtype == jnp.bfloat16
    assert manifest["metadata"]["loss"] == 1.5


def test_checkpoint_gc_keeps_latest(tmp_path):
    tree = {"w": jnp.zeros(2)}
    for s in range(5):
        save(str(tmp_path), s, tree, keep=2)
    files = [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
    assert len(files) == 2
    assert latest_step(str(tmp_path)) == 4


def test_checkpoint_shape_mismatch_raises(tmp_path):
    save(str(tmp_path), 0, {"w": jnp.zeros((2, 2))})
    with pytest.raises(ValueError):
        restore(str(tmp_path), {"w": jnp.zeros((3,))})
