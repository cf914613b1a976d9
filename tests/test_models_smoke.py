"""Per-architecture smoke tests (deliverable f): reduced config, one
forward/train step, output shapes + no NaNs; decode==prefill consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # Per-architecture forward/train steps compile real models

from repro.configs import get_config, get_smoke_config, list_archs
from repro.models import (DtypePolicy, MoECtx, decode_step,
                          init_params, pad_prefill_caches, prefill, train_loss)

F32 = DtypePolicy(jnp.float32, jnp.float32, jnp.float32)
ARCHS = [a for a in list_archs()]


def mk_batch(cfg, B=2, S=32, seed=0):
    key = jax.random.PRNGKey(seed)
    if cfg.input_mode == "embeddings":
        return {"embeddings": jax.random.normal(key, (B, S, cfg.d_model)),
                "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    return {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
            "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    cfg = get_smoke_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = mk_batch(cfg)
    moe = MoECtx(impl="dropping")
    loss, grads = jax.value_and_grad(
        lambda p: train_loss(p, batch, cfg, moe, remat=True))(params)
    assert jnp.isfinite(loss)
    assert float(loss) < 2.5 * np.log(cfg.vocab_size)
    gn = sum(float(jnp.sum(g.astype(jnp.float32) ** 2))
             for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_shapes_and_finite(arch):
    cfg = get_smoke_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, S = 2, 32
    logits, caches = prefill(params, mk_batch(cfg, B, S), cfg,
                             MoECtx(impl="dropping"), policy=F32)
    if cfg.is_encoder_only:
        assert logits.shape == (B, S, cfg.vocab_size)
        assert caches is None
    else:
        assert logits.shape == (B, 1, cfg.vocab_size)
        assert caches is not None
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_prefill(arch):
    """decode(token S | cache of S) == prefill(S+1)'s last logits — covers
    ring caches, MLA absorbed decode, SSD recurrence vs chunked."""
    cfg = get_smoke_config(arch)
    if cfg.is_encoder_only:
        pytest.skip("encoder-only: no decode step")
    params = init_params(jax.random.PRNGKey(1), cfg)
    B, S = 2, 31
    key = jax.random.PRNGKey(2)
    toks = jax.random.randint(key, (B, S + 1), 0, cfg.vocab_size)
    moe = MoECtx(impl="dense" if cfg.n_experts else "dropping")
    if cfg.input_mode == "embeddings":
        emb = jnp.take(params["embed"], toks, axis=0)
        full, _ = prefill(params, {"embeddings": emb}, cfg, moe, policy=F32)
        pre, caches = prefill(params, {"embeddings": emb[:, :S]}, cfg, moe,
                              policy=F32)
    else:
        full, _ = prefill(params, {"tokens": toks}, cfg, moe, policy=F32)
        pre, caches = prefill(params, {"tokens": toks[:, :S]}, cfg, moe,
                              policy=F32)
    caches = pad_prefill_caches(caches, cfg, S + 8)
    dec, _ = decode_step(params, toks[:, S:S + 1], caches, jnp.int32(S), cfg,
                         moe, policy=F32)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full), atol=2e-3)


DECODERS = [a for a in ARCHS if not get_smoke_config(a).is_encoder_only]
ROW_LENS = (37, 9, 22)   # row 0 lies past every smoke window: its ring wrapped
ROW_T = 48               # positions a full cache holds


def _row_lens(cfg):
    """SSD prefills one chunk (32 in the smoke config) or whole chunks."""
    return (31,) + ROW_LENS[1:] if cfg.family == "ssm" else ROW_LENS


def _prefill_rows(params, cfg, moe, toks, lens):
    """Each row prefilled alone to its own length: the rows' caches padded
    to ``ROW_T`` and joined on the batch axis (axis 1 of the scan's stacked
    caches), and each row's logits after its next token."""
    def run(x):
        batch = ({"embeddings": jnp.take(params["embed"], x, axis=0)}
                 if cfg.input_mode == "embeddings" else {"tokens": x})
        return prefill(params, batch, cfg, moe, policy=F32)

    caches, want = [], []
    for b, n in enumerate(lens):
        _, c = run(toks[b:b + 1, :n])
        caches.append(pad_prefill_caches(c, cfg, ROW_T))
        want.append(run(toks[b:b + 1, :n + 1])[0][0])
    join = lambda path, *t: jnp.concatenate(  # noqa: E731
        t, axis=int(path[0].key == "stack"))
    return jax.tree_util.tree_map_with_path(join, *caches), jnp.stack(want)


def _rows_setup(arch):
    cfg = get_smoke_config(arch)
    params = init_params(jax.random.PRNGKey(1), cfg)
    lens = _row_lens(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2),
                              (len(lens), max(lens) + 1), 0, cfg.vocab_size)
    moe = MoECtx(impl="dense" if cfg.n_experts else "dropping")
    caches, want = _prefill_rows(params, cfg, moe, toks, lens)
    nxt = toks[jnp.arange(len(lens)), jnp.asarray(lens)][:, None]
    return cfg, params, moe, caches, want, nxt, lens


@pytest.mark.parametrize("arch", DECODERS)
def test_decode_rows_match_prefill(arch):
    """Rows at their own positions (a (B,) ``cache_pos``, as the engine's
    slots decode), one past the ring window: each row's decode logits equal
    its own prefill's."""
    cfg, params, moe, caches, want, nxt, lens = _rows_setup(arch)
    dec, _ = decode_step(params, nxt, caches, jnp.asarray(lens), cfg, moe,
                         policy=F32)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(want), atol=2e-3)


@pytest.mark.parametrize("outside", ["one", "every"])
@pytest.mark.parametrize("arch", ["qwen3-4b", "h2o-danube-1.8b",
                                  "deepseek-v2-lite-16b"])
def test_decode_row_outside_cache_writes_nothing(arch, outside):
    """A row whose position lies outside the cache (past a full cache's end;
    negative for a ring, which any position >= 0 maps into) writes nothing:
    the decode writes only the other rows' entries at their positions, and
    with every row outside leaves the caches as they were."""
    from repro.models.attention import TIME_AXIS
    cfg, params, moe, caches, want, nxt, lens = _rows_setup(arch)
    far = -1 if cfg.attn_kind == "swa" else ROW_T
    pos = np.array(lens if outside == "one" else (far,) * 3, np.int32)
    pos[-1] = far
    dec, new = decode_step(params, nxt, caches, jnp.asarray(pos), cfg, moe,
                           policy=F32)

    def check(path, old, got):
        old, got = np.array(old), np.asarray(got)
        b_ax = int(path[0].key == "stack")
        t_ax = b_ax + TIME_AXIS[path[-1].key]
        for b, p in enumerate(pos[:-1] if outside == "one" else ()):
            ix = [slice(None)] * old.ndim
            ix[b_ax], ix[t_ax] = b, p % old.shape[t_ax]
            assert not np.array_equal(old[tuple(ix)], got[tuple(ix)])
            old[tuple(ix)] = got[tuple(ix)]
        np.testing.assert_array_equal(got, old)

    jax.tree_util.tree_map_with_path(check, caches, new)
    if outside == "one":
        np.testing.assert_allclose(np.asarray(dec[:-1]),
                                   np.asarray(want[:-1]), atol=2e-3)


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_parameter_count(arch):
    """Analytic param counts of the FULL configs land near the published
    sizes (sanity for the dry-run/roofline MODEL_FLOPS)."""
    expected = {
        "phi3.5-moe-42b-a6.6b": (42e9, 0.10),
        "deepseek-v2-lite-16b": (15.7e9, 0.15),
        "mamba2-370m": (0.37e9, 0.25),
        "gemma3-4b": (4.3e9, 0.30),
        "minicpm3-4b": (4.0e9, 0.30),
        "qwen3-4b": (4.0e9, 0.25),
        "h2o-danube-1.8b": (1.8e9, 0.25),
        "hubert-xlarge": (0.96e9, 0.30),
        "internvl2-76b": (70e9, 0.15),
        "recurrentgemma-9b": (9e9, 0.35),
        "llama2-13b": (13e9, 0.10),
    }
    cfg = get_config(arch)
    n = cfg.param_count()
    target, tol = expected[arch]
    assert abs(n - target) / target < tol, f"{arch}: {n/1e9:.2f}B vs {target/1e9}B"


def test_moe_dense_vs_dropping_high_capacity():
    """With capacity >= tokens, the dropping path must equal dense routing."""
    cfg = get_smoke_config("phi3.5-moe-42b-a6.6b").scaled(capacity_factor=8.0)
    params = init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    from repro.models.moe import init_moe, moe_dense, moe_dropping
    mp = init_moe(jax.random.PRNGKey(2), cfg, jnp.float32)
    y1, _ = moe_dense(mp, x, cfg)
    y2, _ = moe_dropping(mp, x, cfg)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4,
                               rtol=1e-4)
