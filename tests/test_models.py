"""Per-architecture smoke tests: reduced same-family config, one forward +
one train step on CPU, asserting output shapes and no NaNs (the FULL
configs are exercised only via the dry-run)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get
from repro.data import glyph_batch
from repro.models import DarkNetLike, LeNet
from repro.models.spec import init_params
from repro.optim import AdamW, constant
from repro.train import make_train_step, init_state

ARCH_NAMES = sorted(ARCHS)


def _batch_for(arch, model, b=2, s=16):
    cfg = model.cfg
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab)
    tgt = jnp.roll(toks, -1, axis=1)
    mask = jnp.ones((b, s), jnp.float32)
    if arch.kind == "encdec":
        frames = jax.random.normal(jax.random.PRNGKey(2), (b, s, cfg.d_model),
                                   jnp.float32).astype(jnp.bfloat16)
        return {"frames": frames, "tokens": toks, "targets": tgt, "mask": mask}
    out = {"tokens": toks, "targets": tgt, "mask": mask}
    if getattr(cfg, "vlm_prefix", 0):
        out["patch_embeds"] = jnp.ones((b, cfg.vlm_prefix, cfg.d_model),
                                       jnp.bfloat16)
    return out


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_reduced_forward_and_train_step(name):
    arch = get(name)
    model = arch.build_reduced()
    cfg = model.cfg
    params = init_params(model.specs(), jax.random.PRNGKey(0))
    batch = _batch_for(arch, model)

    if arch.kind == "encdec":
        logits = model.forward(params, batch["frames"], batch["tokens"])
        assert logits.shape == (2, 16, cfg.padded_vocab)
        def loss_fn(p, b):
            return model.loss(p, b["frames"], b["tokens"], b["targets"], b["mask"])
    else:
        logits, _ = model.forward(params, batch["tokens"],
                                  batch.get("patch_embeds"))
        prefix = getattr(cfg, "vlm_prefix", 0)
        assert logits.shape == (2, 16 + prefix, cfg.padded_vocab)
        def loss_fn(p, b):
            return model.loss(p, b["tokens"], b["targets"], b["mask"],
                              b.get("patch_embeds"))
    assert not bool(jnp.any(jnp.isnan(logits)))

    opt = AdamW(constant(1e-3), state_dtype=arch.optimizer_state)
    step = jax.jit(make_train_step(loss_fn, opt))
    state = init_state(params, opt)
    state, metrics = step(state, batch)
    assert jnp.isfinite(metrics["loss"])
    # params actually changed
    moved = any(bool(jnp.any(a != b)) for a, b in
                zip(jax.tree.leaves(state.params), jax.tree.leaves(params)))
    assert moved


@pytest.mark.parametrize("name", [n for n in ARCH_NAMES
                                  if ARCHS[n].kind == "lm"])
def test_reduced_decode_matches_vocab(name):
    arch = get(name)
    model = arch.build_reduced()
    cfg = model.cfg
    if getattr(cfg, "vlm_prefix", 0):
        pytest.skip("decode exercised without vision prefix elsewhere")
    params = init_params(model.specs(), jax.random.PRNGKey(0))
    b, s = 2, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab)
    logits, cache = model.prefill(params, toks, context=32)
    assert logits.shape == (b, cfg.padded_vocab)
    tok = jnp.argmax(logits, -1)
    # padded-vocab logits are masked: argmax stays within the true vocab
    assert int(tok.max()) < cfg.vocab
    logits2, cache = model.decode_step(params, tok, cache,
                                       jnp.full((b,), s, jnp.int32))
    assert logits2.shape == (b, cfg.padded_vocab)
    assert not bool(jnp.any(jnp.isnan(logits2)))


def test_encdec_decode_step():
    arch = get("whisper-medium")
    model = arch.build_reduced()
    params = init_params(model.specs(), jax.random.PRNGKey(0))
    frames = jax.random.normal(jax.random.PRNGKey(2), (2, 12, model.cfg.d_model))
    mem = model.encode(params, frames)
    # both cache flavors: precomputed cross k/v (production) and legacy
    lg_pre = lg_legacy = None
    for params_arg in (params, None):
        cache = model.init_cache(2, 16, mem, params_arg)
        lg, cache = model.decode_step(params, jnp.zeros((2,), jnp.int32),
                                      cache, jnp.zeros((2,), jnp.int32))
        assert lg.shape == (2, model.cfg.padded_vocab)
        assert not bool(jnp.any(jnp.isnan(lg)))
        if params_arg is not None:
            lg_pre = lg
        else:
            lg_legacy = lg
    # same math either way (bf16 rounding of the cached k/v only)
    assert float(jnp.max(jnp.abs(lg_pre - lg_legacy))) < 0.25
    assert int(jnp.argmax(lg_pre)) == int(jnp.argmax(lg_legacy))


def test_swa_prefill_ring_cache_consistency():
    """Decoding right after an SWA prefill must attend the same window a
    full forward sees: compare next-token logits against a one-longer
    forward pass."""
    arch = get("h2o-danube-3-4b")
    model = arch.build_reduced()
    cfg = model.cfg
    params = init_params(model.specs(), jax.random.PRNGKey(0))
    b, s = 1, 24                      # window is 16 in the reduced config
    toks = jax.random.randint(jax.random.PRNGKey(3), (b, s + 1), 0, cfg.vocab)
    logits, cache = model.prefill(params, toks[:, :s], context=s)
    step_logits, _ = model.decode_step(params, toks[:, s], cache,
                                       jnp.full((b,), s, jnp.int32))
    full, _ = model.forward(params, toks)
    ref = full[:, s]
    # bf16 matmuls; compare top-1 and correlation rather than exact values
    assert int(jnp.argmax(step_logits)) == int(jnp.argmax(ref))


def test_long_context_shape_policy():
    sub_q = {n: get(n).supports("long_500k")[0] for n in ARCH_NAMES}
    assert sub_q["h2o-danube-3-4b"] and sub_q["mixtral-8x7b"]
    assert sub_q["recurrentgemma-9b"] and sub_q["xlstm-125m"]
    assert not sub_q["minicpm-2b"] and not sub_q["kimi-k2-1t-a32b"]
    assert not sub_q["whisper-medium"]


def test_assigned_full_configs_match_table():
    """The exact assigned hyperparameters (guards against config drift)."""
    c = get("kimi-k2-1t-a32b").config
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv) == (61, 7168, 64, 8)
    assert (c.n_experts, c.top_k, c.vocab) == (384, 8, 163840)
    c = get("recurrentgemma-9b").config
    assert (c.n_layers, c.d_model, c.pattern) == (38, 4096, ("rec", "rec", "attn"))
    c = get("mixtral-8x7b").config
    assert (c.n_experts, c.top_k, c.window) == (8, 2, 4096)
    c = get("xlstm-125m").config
    assert (c.d_ff, c.pattern) == (0, ("mlstm", "slstm"))
    c = get("whisper-medium").config
    assert (c.n_layers, c.d_model, c.vocab) == (24, 1024, 51865)


@pytest.mark.parametrize("model", [LeNet(), DarkNetLike()],
                         ids=["lenet", "darknet"])
def test_layer_traffic_made_on_host(model):
    """The glyph input and the traffic extraction are computed on the host
    CPU and handed back as uncommitted default-device arrays, bit-identical
    to the extraction called directly (a committed CPU array would pull the
    packetizer off an accelerator)."""
    params = init_params(model.specs(), jax.random.PRNGKey(0))
    x, labels = glyph_batch(jax.random.PRNGKey(7), 1, hw=model.input_shape[0],
                            channels=model.input_shape[-1])
    assert not x.committed and not labels.committed
    got = model.layer_traffic(params, x[0])
    want = type(model).layer_traffic.__wrapped__(model, params, x[0])
    assert len(got) == len(want)
    for g, w in zip(got, want):
        for a, b in ((g.inputs, w.inputs), (g.weights, w.weights)):
            assert not a.committed
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
