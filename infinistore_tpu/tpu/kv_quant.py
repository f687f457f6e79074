"""int8 KV-cache quantization: half the HBM traffic per decode token, half
the store bytes per cached block.

Decode attention is HBM-bandwidth-bound (paged_attention.py), so the cache's
dtype IS its speed — and the store's capacity doubles for free. This module
provides the symmetric per-(token, head) int8 scheme TPU serving stacks use:

- ``quantize_kv(x)`` -> (int8 data, f32 scales): scale = absmax / 127 over
  each (token, head) vector of ``head_dim`` values. Per-vector scaling keeps
  the error at RoPE'd-key scale (a single per-block scale would be hostage
  to one outlier token).
- ``dequantize_kv(data, scales)`` -> the float cache (any target dtype).
- ``paged_decode_attention_quantized``: the fused decode kernel over int8
  caches — blocks are DMA'd at int8 width (the bandwidth win) and
  dequantized in VMEM right before the dots, with the same online-softmax
  and the same f32 statistics as the float kernel.

The scales array is [N, bt, KVH] f32 — 1/head_dim of the data bytes — and
rides to the store as its own tiny blocks (`connector.py` works on any
dtype; a quantized engine binds one connector for data and one for scales
over the same chain keys, tested in tests/test_kv_quant.py).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import wire
from . import paged


@jax.jit
def quantize_kv(x: jax.Array):
    """Symmetric int8 per-(token, head) quantization.

    x: [..., head_dim] float; returns (int8 of x's shape, f32 scales of
    x.shape[:-1]). Zero vectors get scale 0 and dequantize to exact zeros.
    """
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = absmax / 127.0
    inv = jnp.where(scale > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) * inv[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


@functools.partial(jax.jit, static_argnames=("dtype",))
def dequantize_kv(data: jax.Array, scales: jax.Array, dtype=jnp.float32):
    """Inverse of quantize_kv: data [..., D] int8, scales [...] f32."""
    return (data.astype(jnp.float32) * scales[..., None]).astype(dtype)


# ---------------------------------------------------------------------------
# Fused decode attention over int8 caches.
# ---------------------------------------------------------------------------


def _quant_decode_kernel(
    table_ref,  # scalar-prefetch: [B, max_blocks] int32
    seqlen_ref,  # scalar-prefetch: [B] int32
    q_ref,  # [1, H, D] float query
    kpos_ref,  # [H, bt * KVH] int32 (paged_attention._key_positions)
    k_ref,  # [1, bt * KVH, D] int8, a page as it lies in the cache
    ks_ref,  # [1, bt * KVH, 1] f32 scales
    v_ref,  # [1, bt * KVH, D] int8
    vs_ref,  # [1, bt * KVH, 1] f32
    out_ref,  # [1, H, D]
    m_scr,  # VMEM [H, 128] f32
    l_scr,  # VMEM [H, 128] f32
    acc_scr,  # VMEM [H, D] f32
    *,
    bt,
):
    from .paged_attention import _attn_fold

    del table_ref
    b = pl.program_id(0)
    i = pl.program_id(1)
    # Dequantize in VMEM — the HBM read was int8 width — then delegate to
    # the SAME online-softmax update the float kernels use (one copy of the
    # numeric contract, paged_attention.py; f32 operands, so its dots ask
    # Precision.HIGHEST). This grid is (row, block in row): a step is one
    # page, the grid step its index.
    _attn_fold(
        i == 0,
        i * bt,
        seqlen_ref[b],
        1.0 / np.sqrt(q_ref.shape[-1]),
        q_ref[0],
        k_ref[0].astype(jnp.float32) * ks_ref[0],
        v_ref[0].astype(jnp.float32) * vs_ref[0],
        kpos_ref[...],
        m_scr,
        l_scr,
        acc_scr,
    )

    @pl.when(i == pl.num_programs(1) - 1)
    def _finish():
        out_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
        ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _quant_decode_pallas(
    q, k_data, k_scales, v_data, v_scales, block_tables, seq_lens, *, interpret
):
    from .paged_attention import _key_positions

    bsz, h, d = q.shape
    nb, bt, kvh, _ = k_data.shape
    n = block_tables.shape[1]
    # A page as it lies in the cache: [bt * KVH, D], a row a (key, KV head).
    rows = bt * kvh
    data_block = (1, rows, d)
    scale_block = (1, rows, 1)
    page = lambda b, i, tbl, sl: (tbl[b, i], 0, 0)
    key_pos = _key_positions(h, kvh, bt)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, n),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda b, i, tbl, sl: (b, 0, 0)),
            pl.BlockSpec(key_pos.shape, lambda b, i, tbl, sl: (0, 0)),
            pl.BlockSpec(data_block, page),
            pl.BlockSpec(scale_block, page),
            pl.BlockSpec(data_block, page),
            pl.BlockSpec(scale_block, page),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda b, i, tbl, sl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )
    seq_lens = jnp.asarray(seq_lens, dtype=jnp.int32).reshape(bsz)
    return pl.pallas_call(
        functools.partial(_quant_decode_kernel, bt=bt),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, h, d), q.dtype),
        interpret=interpret,
    )(
        block_tables, seq_lens, q, key_pos,
        k_data.reshape(nb, rows, d), k_scales.reshape(nb, rows, 1),
        v_data.reshape(nb, rows, d), v_scales.reshape(nb, rows, 1),
    )


@jax.jit
def _quant_decode_xla(q, k_data, k_scales, v_data, v_scales, block_tables, seq_lens):
    """Fallback: dequantize the caches, then the float batched path (the
    identical numeric contract lives there)."""
    from .paged_attention import paged_decode_attention_xla_batched

    return paged_decode_attention_xla_batched(
        q,
        dequantize_kv(k_data, k_scales),
        dequantize_kv(v_data, v_scales),
        block_tables,
        seq_lens,
    )


class QuantizedKVConnector:
    """Store glue for an int8 paged cache: half the bytes per cached block.

    A quantized engine's cache is (int8 data, f32 scales) per K/V side. This
    binds TWO ``KVConnector``s over the same chain keys — one for the data
    blocks (int8, half the float bytes), one for the scale blocks (1/head_dim
    of the data bytes) — and keeps the commit order safe: scales are saved
    BEFORE data, so the data connector's layer-0 sentinel (what ``lookup``
    probes) commits last and a hit implies the scales are present too. A
    scales load that still races eviction degrades to a full miss
    (recompute), never a half-loaded cache.

    Total stored bytes per block: data/2 + data/(2*head_dim) vs data — a
    ~2x capacity win for the same pool, on top of the kernel's bandwidth
    story (paged_decode_attention_quantized).
    """

    def __init__(self, conn, spec, model_id: str, max_blocks: int):
        """``spec``: the FLOAT cache spec the engine would use unquantized
        (its dtype is ignored for storage — data rides int8, scales f32)."""
        from .paged import PagedKVCacheSpec

        # Deferred import: connector pulls in the layerwise machinery.
        from ..connector import KVConnector

        self.spec = spec
        data_spec = PagedKVCacheSpec(
            num_layers=spec.num_layers,
            num_blocks=spec.num_blocks,
            block_tokens=spec.block_tokens,
            num_kv_heads=spec.num_kv_heads,
            head_dim=spec.head_dim,
            dtype=jnp.int8,
        )
        scale_spec = PagedKVCacheSpec(
            num_layers=spec.num_layers,
            num_blocks=spec.num_blocks,
            block_tokens=spec.block_tokens,
            num_kv_heads=spec.num_kv_heads,
            head_dim=1,
            dtype=jnp.float32,
        )
        self.data = KVConnector(conn, data_spec, f"{model_id}/q8", max_blocks)
        self.scales = KVConnector(conn, scale_spec, f"{model_id}/q8s", max_blocks)

    def lookup(self, token_ids) -> int:
        """Blocks cached (data sentinel; commit order makes it imply scales)."""
        return self.data.lookup(token_ids)

    async def save(self, token_ids, quant_caches, block_ids, first_block: int = 0):
        """quant_caches: per layer ((k_int8, k_scales), (v_int8, v_scales)).
        Returns data blocks written."""
        scale_caches = [
            (ks[..., None], vs[..., None]) for (_, ks), (_, vs) in quant_caches
        ]
        data_caches = [(kq, vq) for (kq, _), (vq, _) in quant_caches]
        await self.scales.save(
            token_ids, scale_caches, block_ids, first_block=first_block
        )
        return await self.data.save(
            token_ids, data_caches, block_ids, first_block=first_block
        )

    async def load(
        self, token_ids, quant_caches, block_ids, first_block: int = 0,
        on_layer=None,
    ):
        """Fetch the cached prefix into (data, scales) caches. Returns
        (updated quant_caches, blocks_loaded); a scales race degrades to a
        miss. Data/scale caches are donated — use the returned ones. A
        transport error mid-read re-raises PartialReadError whose
        ``caches`` carry the ZIPPED quantized structure (the donated-buffer
        contract the base connector has, tpu/layerwise.py).

        ``first_block``/``on_layer``: same contract as KVConnector.load.
        A quantized layer is usable only once BOTH its data and scales
        landed, so the hook fires during the scales pass (the data pass
        completed first) with the zipped ((k_int8, k_scales), (v_int8,
        v_scales)) pair."""
        from .layerwise import PartialReadError

        data_caches = [(kq, vq) for (kq, _), (vq, _) in quant_caches]
        scale_caches = [
            (ks[..., None], vs[..., None]) for (_, ks), (_, vs) in quant_caches
        ]
        try:
            data_out, n = await self.data.load(
                token_ids, data_caches, block_ids, first_block=first_block
            )
        except PartialReadError as e:
            raise PartialReadError(
                self._zip(e.caches, scale_caches), e.cause
            ) from e.cause
        if n == 0:
            return self._zip(data_out, scale_caches), 0

        def scale_hook(layer, pair):
            (ks, vs) = pair
            on_layer(
                layer,
                ((data_out[layer][0], ks[..., 0]), (data_out[layer][1], vs[..., 0])),
            )

        try:
            scale_out, ns = await self.scales.load(
                token_ids, scale_caches, block_ids, first_block=first_block,
                on_layer=scale_hook if on_layer is not None else None,
            )
        except PartialReadError as e:
            # The already-donated data caches must travel with the error or
            # the engine is left with deleted buffers on TPU.
            raise PartialReadError(
                self._zip(data_out, e.caches), e.cause
            ) from e.cause
        if ns < n:
            # Scales raced away after the data hit: the data alone is
            # useless — report a miss (cache semantics; engine recomputes).
            return self._zip(data_out, scale_out), 0
        return self._zip(data_out, scale_out), n

    def stage_layer_save(
        self, token_ids, layer: int, kv_pair, block_ids, first_block: int = 0,
        priority: int = wire.PRIORITY_BACKGROUND,
    ):
        """Layer-granular save (KVConnector.stage_layer_save contract) for
        a quantized layer ``((k_int8, k_scales), (v_int8, v_scales))``.
        The returned ship puts scales BEFORE data, preserving the commit
        order the class relies on; layer-by-layer callers (vllm_v1) defer
        layer 0's ship to last, so the data sentinel still commits after
        everything — scales layers 1+, data layers 1+, scales 0, data 0.
        ``priority`` rides both underlying ships (docs/qos.md)."""
        (kq, ks), (vq, vs) = kv_pair
        ship_scales = self.scales.stage_layer_save(
            token_ids, layer, (ks[..., None], vs[..., None]), block_ids,
            first_block=first_block, priority=priority,
        )
        ship_data = self.data.stage_layer_save(
            token_ids, layer, (kq, vq), block_ids, first_block=first_block,
            priority=priority,
        )

        async def ship() -> int:
            await ship_scales()
            return await ship_data()

        return ship

    @staticmethod
    def _zip(data_caches, scale_caches):
        return [
            ((kq, ks[..., 0]), (vq, vs[..., 0]))
            for (kq, vq), (ks, vs) in zip(data_caches, scale_caches)
        ]

    def drop(self, token_ids) -> int:
        """Remove this prompt's data AND scale blocks."""
        return self.data.drop(token_ids) + self.scales.drop(token_ids)

    @property
    def conn(self):
        """The shared store connection (both planes ride one connection) —
        the surface the cluster's probe-heal and the membership resharder
        move raw bytes through."""
        return self.data.conn

    def manifest(self, token_ids, n_blocks=None):
        """Size-grouped key inventory for the resharder (see
        ``KVConnector.manifest``): the scale group precedes the data group,
        mirroring ``save``'s commit order — the data plane's layer-0 K
        sentinel lands last, so a half-migrated copy never looks complete
        to ``lookup``."""
        return self.scales.manifest(token_ids, n_blocks) + self.data.manifest(
            token_ids, n_blocks
        )

    def get_stats(self) -> dict:
        """Connection stats (both planes ride one connection)."""
        return self.data.get_stats()


def paged_decode_attention_quantized(
    q, k_data, k_scales, v_data, v_scales, block_tables, seq_lens
):
    """Batched decode attention over an int8 paged cache.

    q: [B, H, D] float; k/v_data: [N, bt, KVH, D] int8 with f32 scales
    [N, bt, KVH] (from quantize_kv); block_tables [B, max_blocks];
    seq_lens [B] (a zero row returns zeros). Returns [B, H, D] in q's
    dtype. The TPU kernel DMAs blocks at int8 width and dequantizes in
    VMEM; outputs equal attention over the dequantized cache to f32
    rounding (the quantization error itself is the int8 scheme's, measured
    in tests)."""
    if paged._use_pallas():
        return _quant_decode_pallas(
            q, k_data, k_scales, v_data, v_scales, block_tables, seq_lens,
            interpret=False,
        )
    return _quant_decode_xla(
        q, k_data, k_scales, v_data, v_scales, block_tables, seq_lens
    )


class QuantizingKVAdapter:
    """EngineKVAdapter-shaped surface that compresses a FLOAT engine cache
    to int8 on the way to the store, transparently.

    The engine keeps its float paged cache and its block tables exactly as
    with the plain adapter (engine.py EngineKVAdapter); only the store
    bytes change: ``save_kv`` gathers the request's float blocks, quantizes
    them on device, and ships int8 + scales; ``load_kv`` fetches int8 +
    scales and scatters dequantized floats back into the engine's cache.
    ~2x cached context per pool at the int8 scheme's error — a harness
    verifying against the prefill oracle must use a quantization-aware
    tolerance (ContinuousBatchingHarness(verify_tol=...)).
    """

    def __init__(self, qconn: "QuantizedKVConnector"):
        self.qconn = qconn
        self.block_tokens = qconn.spec.block_tokens
        self._nq = qconn.spec.num_blocks  # staging rows for fetch/ship

    def _fresh_quant(self, rows: int):
        spec = self.qconn.spec
        shape = (rows, spec.block_tokens, spec.num_kv_heads, spec.head_dim)
        return [
            (
                (jnp.zeros(shape, jnp.int8), jnp.zeros(shape[:-1], jnp.float32)),
                (jnp.zeros(shape, jnp.int8), jnp.zeros(shape[:-1], jnp.float32)),
            )
            for _ in range(spec.num_layers)
        ]

    def get_num_matched_tokens(self, token_ids) -> int:
        return self.qconn.lookup(token_ids) * self.block_tokens

    async def save_kv(self, token_ids, caches, block_table, first_block: int = 0):
        """Gather the float blocks, quantize, ship int8 + scales. ``caches``
        may be the engine's full cache (gathered at ``block_table``) or
        already-gathered block arrays with an identity table."""
        from .paged import gather_blocks

        n = len(block_table)
        ids = jnp.asarray(np.asarray(block_table), jnp.int32)
        quant = []
        for k_cache, v_cache in caches:
            kb = gather_blocks(k_cache, ids)
            vb = gather_blocks(v_cache, ids)
            quant.append((quantize_kv(kb), quantize_kv(vb)))
        return await self.qconn.save(
            token_ids, quant, np.arange(n, dtype=np.int32), first_block=first_block
        )

    async def load_kv(self, token_ids, caches, block_table):
        """Fetch int8 + scales, dequantize, scatter into the engine's float
        cache blocks. Returns (updated caches, tokens_loaded). The float
        ``caches`` are donated by the scatters — use the returned ones."""
        from .paged import scatter_blocks

        # One control RTT total: qconn.load does its own prefix lookup and
        # caps by the staging ids. Staging rows are bounded by the spec's
        # num_blocks (a longer hit loads a shorter prefix; the engine
        # computes the rest — never an out-of-bounds scatter).
        n = min(len(block_table), self._nq)
        if n == 0:
            return list(caches), 0
        staged, got = await self.qconn.load(
            token_ids, self._fresh_quant(n), np.arange(n, dtype=np.int32)
        )
        if got == 0:
            return list(caches), 0
        ids = jnp.asarray(np.asarray(block_table[:got]), jnp.int32)
        out = []
        for (k_cache, v_cache), ((kq, ks), (vq, vs)) in zip(caches, staged):
            dtype = k_cache.dtype
            k_blocks = dequantize_kv(kq[:got], ks[:got], dtype=dtype)
            v_blocks = dequantize_kv(vq[:got], vs[:got], dtype=dtype)
            out.append(
                (
                    scatter_blocks(k_cache, ids, k_blocks),
                    scatter_blocks(v_cache, ids, v_blocks),
                )
            )
        return out, got * self.block_tokens

    def evict_request(self, token_ids) -> int:
        return self.qconn.drop(token_ids)
