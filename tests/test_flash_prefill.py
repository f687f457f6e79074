"""Flash (blocked online-softmax) prefill attention: the Pallas kernel in
interpret mode against a float64 numpy oracle — causal and full, GQA ratios,
block-size boundaries — plus the dispatcher contract the model's prefill
relies on (mask=None routes causal attention through it)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from infinistore_tpu.tpu.flash_prefill import (
    _flash_prefill_pallas,
    flash_prefill_attention,
    flash_prefill_xla,
)


def _oracle(q, k, v, causal):
    q = np.asarray(q, np.float64)
    k = np.asarray(k, np.float64)
    v = np.asarray(v, np.float64)
    b, s, h, d = q.shape
    groups = h // k.shape[2]
    k = np.repeat(k, groups, axis=2)
    v = np.repeat(v, groups, axis=2)
    logits = np.einsum("bshd,bthd->bhst", q, k) / np.sqrt(d)
    if causal:
        t = k.shape[1]
        cm = np.arange(s)[:, None] >= np.arange(t)[None, :]
        logits = np.where(cm[None, None], logits, -np.inf)
    p = np.exp(logits - logits.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    return np.einsum("bhst,bthd->bshd", p, v)


CASES = [
    # (B, S, H, KVH, D, block_q, block_k)
    (1, 32, 4, 2, 16, 8, 8),  # GQA x2, several blocks
    (2, 64, 8, 8, 32, 16, 32),  # MHA, batch 2, uneven bq/bk
    (1, 16, 4, 1, 64, 16, 16),  # MQA, single block each way
    (1, 48, 2, 2, 16, 8, 24),  # bk > bq
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("causal", [True, False])
def test_kernel_matches_oracle(case, causal):
    b, s, h, kvh, d, bq, bk = case
    rng = np.random.default_rng(abs(hash((case, causal))) % 2**32)
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kvh, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kvh, d)), jnp.float32)
    want = _oracle(q, k, v, causal)
    got = _flash_prefill_pallas(
        q, k, v, causal=causal, block_q=bq, block_k=bk, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float64), want, rtol=1e-5, atol=1e-5
    )
    got_xla = flash_prefill_xla(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(got_xla, np.float64), want, rtol=1e-5, atol=1e-5
    )


def test_awkward_lengths_pad_to_aligned_blocks():
    """Lengths that are not a whole number of tile-aligned blocks are padded
    and masked, never handed to the TPU lowering as an unaligned block (a
    264-token prompt used to pick bq=132, which Mosaic rejects — interpret
    mode never noticed; tests/test_tpu_aot_compile.py compiles it now)."""
    from infinistore_tpu.tpu.flash_prefill import _block_and_padded

    assert _block_and_padded(264, 256, 16) == (144, 288)
    assert _block_and_padded(272, 256, 16) == (144, 288)
    assert _block_and_padded(1024, 256, 16) == (256, 1024)
    assert _block_and_padded(48, 256, 16) == (48, 48)
    assert _block_and_padded(20, 8, 8) == (8, 24)
    assert _block_and_padded(17, 8, 8) == (8, 24)
    rng = np.random.default_rng(9)
    for causal in (True, False):
        # S != T off the causal path: both paddings and the kv mask at once.
        s_len, t_len = (20, 20) if causal else (20, 27)
        q = jnp.asarray(rng.standard_normal((1, s_len, 2, 16)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, t_len, 2, 16)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, t_len, 2, 16)), jnp.float32)
        got = _flash_prefill_pallas(
            q, k, v, causal=causal, block_q=8, block_k=8, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(got, np.float64), _oracle(q, k, v, causal),
            rtol=1e-5, atol=1e-5,
        )


def test_dispatcher_is_dense_off_tpu():
    """On non-TPU backends the dispatcher must be the XLA dense path (the
    model's prefill routes mask=None through it, and CPU tests rely on the
    dense numerics)."""
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((1, 16, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 16, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 16, 2, 16)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(flash_prefill_attention(q, k, v)),
        np.asarray(flash_prefill_xla(q, k, v)),
    )


def test_prefill_still_matches_decode_through_flash_route():
    """The model's prefill now routes causal attention through the flash
    dispatcher; the paged-decode == full-prefill invariant must hold."""
    from infinistore_tpu.models import LlamaConfig, decode_step, init_params, prefill

    cfg = LlamaConfig(
        vocab=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=64,
        block_tokens=8, dtype=jnp.float32,
    )
    params = init_params(cfg, jax.random.PRNGKey(2))
    full = jax.random.randint(jax.random.PRNGKey(3), (24,), 0, cfg.vocab)
    table = jnp.asarray([0, 1, 2, 3], jnp.int32)
    caches = cfg.kv_spec(8).make_caches()
    ref_logits, _ = prefill(
        params, full, cfg.kv_spec(8).make_caches(), table[:3], cfg
    )
    logits, caches = prefill(params, full[:16], caches, table[:2], cfg)
    for pos in range(16, 24):
        logits, caches = decode_step(
            params, full[pos], jnp.int32(pos), caches, table, cfg, 4
        )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
    )
