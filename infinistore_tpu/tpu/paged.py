"""Paged KV-cache block ops: Pallas gather/scatter between the paged HBM cache
and contiguous staging-bound buffers.

The reference never touches KV layout — CUDA engines hand it raw device
pointers and GPUDirect does the rest. On TPU the engine's KV cache is a paged
jax.Array of shape [num_blocks, block_tokens, num_kv_heads, head_dim] (the
layout used by TPU ragged paged attention kernels, per PAPERS.md), and
extracting a request's blocks for offload — or re-inserting fetched blocks —
is a gather/scatter over dynamic block ids. Those are the hot device-side ops
of the store, so they get Pallas kernels (scalar-prefetched block ids drive
the DMA index maps; the copy itself is a pipelined HBM->VMEM->HBM move with no
compute) with pure-XLA fallbacks for non-TPU backends and debugging.
"""

import functools
from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


@dataclass(frozen=True)
class CacheTensor:
    """One named per-block tensor of a layer's cache: a block of it is one
    value in the store, under the key kind ``name``. The hit policy is the
    TENSOR's: one layer may hold tensors fetched in every block of a hit (a K
    and a V) beside tensors fetched in its last block alone (a recurrent
    state and its convolution tail)."""

    name: str  # the store key's kind: "k", "v", "latent", "state", "tail"
    block_shape: Tuple[int, ...]  # one block's shape (the cache adds a leading block axis)
    dtype: jnp.dtype = jnp.bfloat16
    # The hit policy: None, every block of a hit is fetched and installed;
    # a count, the hit's trailing ``last_blocks`` blocks only (1: a recurrent
    # state, which the last block's value replaces whole; window /
    # block_tokens: a sliding layer). Every block is SAVED either way.
    last_blocks: Optional[int] = None
    # What the ledger counts it as: "kv", "latent", "index" (a latent layer's
    # index keys; all three grow with the prefix) or "state" (a recurrent
    # layer's state and its convolution tail).
    kind: str = "kv"
    # Of a "state": whether it ABSORBS its tokens, each once (a recurrence's
    # state, a convolution's tail). False: a block's checkpoint that a token
    # at the same position rewrites whole, as it does a K/V slot (the hidden
    # row at a block's last position that a drafting layer's hit resumes
    # from): counted as state, served as a slot.
    recurrent: bool = True

    @functools.cached_property
    def nbytes(self) -> int:
        """Bytes of one block (reckoned once: the data plane asks a block at a time)."""
        return int(np.prod(self.block_shape)) * jnp.dtype(self.dtype).itemsize

    def hit_first(self, n_blocks: int) -> int:
        """First block of an ``n_blocks`` prefix that a hit fetches."""
        return 0 if self.last_blocks is None else max(0, n_blocks - self.last_blocks)


@dataclass(frozen=True)
class PagedKVCacheSpec:
    """Shape contract for one model's paged cache: per layer, a tuple of
    named per-block tensors (:class:`CacheTensor`), each with its hit
    policy. The common case, a K and a V of ONE shape for all layers, is
    written with the scalar fields (``num_kv_heads``, ``head_dim``,
    ``dtype``, ``windows``) and ``layers`` left None; a cache whose layers
    differ in kind (a latent layer beside a recurrent one), or whose every
    layer is MIXED (``(k, v, state, tail)``: two policies and two dtypes in
    one layer, ``has_state`` and a page list true together), names ``layers``
    and leaves the K/V fields at 0 (:meth:`of_layers`). Whoever asks what a
    hit fetches asks a tensor (``CacheTensor.hit_first``; ``hit_values``,
    ``hit_nbytes`` and the data plane's ``_layer_plan`` do)."""

    num_layers: int
    num_blocks: int
    block_tokens: int
    num_kv_heads: int
    head_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    # Per layer, the sliding window in tokens (a multiple of block_tokens)
    # or None for a layer that attends its whole context; None for a model
    # whose layers all do. From it alone the data plane derives what a hit
    # installs (``hit_first_block``) and the wave its second page list.
    windows: Optional[Tuple[Optional[int], ...]] = None
    # Per layer, its tensors; None: a K and a V of ``block_shape`` a layer,
    # a sliding layer's with the window's policy.
    layers: Optional[Tuple[Tuple[CacheTensor, ...], ...]] = None

    def __post_init__(self):
        if self.layers is not None:
            if len(self.layers) != self.num_layers or not all(self.layers):
                raise ValueError(
                    f"layers names {len(self.layers)} layers' tensors, the cache has {self.num_layers}"
                )
            if self.windows is not None:
                raise ValueError("a cache of named tensors states a window as a tensor's last_blocks")
            return
        if self.windows is None:
            return
        if len(self.windows) != self.num_layers:
            raise ValueError(
                f"windows names {len(self.windows)} layers, the cache has {self.num_layers}"
            )
        for w in self.windows:
            if w is not None and (w <= 0 or w % self.block_tokens):
                raise ValueError(
                    f"a window of {w} tokens is no whole number of {self.block_tokens}-token blocks"
                )

    @classmethod
    def of_layers(cls, num_blocks: int, block_tokens: int, layers) -> "PagedKVCacheSpec":
        """A cache of per-layer kinds of named tensors."""
        layers = tuple(tuple(tensors) for tensors in layers)
        return cls(len(layers), num_blocks, block_tokens, 0, 0, None, None, layers)

    @property
    def uniform(self) -> bool:
        """A K and a V of one shape for all layers: what the scalar fields say."""
        return self.layers is None

    def layer_tensors(self, layer: int) -> Tuple[CacheTensor, ...]:
        """Layer ``layer``'s tensors, in the order of its cache tuple."""
        return self._tensors[layer]

    @functools.cached_property
    def _tensors(self) -> Tuple[Tuple[CacheTensor, ...], ...]:
        """Every layer's tensors, made once a spec: the data plane asks per
        layer and per block."""
        if self.layers is not None:
            return self.layers

        def pair(layer: int):
            w = self.windows[layer] if self.windows else None
            last = None if w is None else w // self.block_tokens
            return tuple(
                CacheTensor(name, self.block_shape, self.dtype, last) for name in ("k", "v")
            )

        return tuple(pair(layer) for layer in range(self.num_layers))

    @property
    def has_state(self) -> bool:
        """Whether a layer keeps a recurrent state: what a block holds of it
        is the state at the block's end, so a token is absorbed ONCE (the
        engine lands a prompt's last token in the first wave alone)."""
        return self.layers is not None and any(
            t.kind == "state" and t.recurrent for tensors in self.layers for t in tensors
        )

    @property
    def window(self) -> Optional[int]:
        """The window of the cache's sliding layers (one size a model), or
        None where every layer attends its whole context."""
        sizes = {w for w in self.windows or () if w is not None}
        if len(sizes) > 1:
            raise ValueError(f"sliding layers of unlike windows: {sorted(sizes)}")
        return sizes.pop() if sizes else None

    def hit_first_block(self, layer: int, n_blocks: int) -> int:
        """First block of an ``n_blocks`` prefix that a hit fetches and
        installs for ``layer``, where its tensors agree (of a K/V layer,
        both): 0 for a full layer, and for a sliding one the first of its last
        ``window / block_tokens`` blocks. A question token at prefix position
        P + i sees back to P + i - window + 1, which lies in block ``n_blocks
        - window / block_tokens`` or later. Every block of every layer is
        still SAVED, so that any shorter prefix can resume. A layer whose
        tensors differ in policy has no one answer: ``ValueError``, ask the
        tensor (``CacheTensor.hit_first``)."""
        firsts = {t.hit_first(n_blocks) for t in self.layer_tensors(layer)}
        if len(firsts) > 1:
            raise ValueError(
                f"layer {layer}'s tensors differ in hit policy (first blocks {sorted(firsts)} of "
                f"{n_blocks}): ask the tensor's hit_first"
            )
        return firsts.pop()

    def hit_values(self, n_blocks: int) -> Tuple[int, int]:
        """(trailing, whole): the store values (one block of one tensor of
        one layer) a hit of ``n_blocks`` fetches of tensors whose policy is
        the hit's trailing blocks (a sliding layer's K and V, a state) and of
        those fetched in every block."""
        trailing = whole = 0
        for layer in range(self.num_layers):
            for t in self.layer_tensors(layer):
                count = n_blocks - t.hit_first(n_blocks)
                if t.last_blocks is None:
                    whole += count
                else:
                    trailing += count
        return trailing, whole

    def hit_nbytes(self, layer: int, n_blocks: int) -> int:
        """Bytes a hit of ``n_blocks`` fetches for ``layer``."""
        return sum(
            (n_blocks - t.hit_first(n_blocks)) * t.nbytes for t in self.layer_tensors(layer)
        )

    def region_nbytes(self, n_blocks: int) -> int:
        """A staging region's size for a hit of ``n_blocks``: the most any
        layer's hit weighs; of a K/V cache, a K and a V of every block."""
        if self.uniform:
            return 2 * n_blocks * self.block_nbytes
        return max(self.hit_nbytes(l, n_blocks) for l in range(self.num_layers))

    def hit_slots(self, n_blocks: int, slot_nbytes: int) -> List[int]:
        """Per layer, the whole staging slots of ``slot_nbytes`` that the
        layer's hit of ``n_blocks`` takes (a prefetch's region a layer)."""
        return [
            -(-self.hit_nbytes(l, n_blocks) // slot_nbytes) for l in range(self.num_layers)
        ]

    @property
    def slot_nbytes(self) -> int:
        """The staging pools' slot: a K block, or the cache's lightest value."""
        if self.uniform:
            return self.block_nbytes
        return min(t.nbytes for tensors in self.layers for t in tensors)

    @property
    def block_shape(self) -> Tuple[int, int, int]:
        return (self.block_tokens, self.num_kv_heads, self.head_dim)

    @property
    def cache_shape(self) -> Tuple[int, int, int, int]:
        return (self.num_blocks, *self.block_shape)

    @property
    def block_nbytes(self) -> int:
        return int(np.prod(self.block_shape)) * jnp.dtype(self.dtype).itemsize

    def make_caches(self) -> List[Tuple[jax.Array, ...]]:
        """Fresh zeroed tensors per layer (a K and a V, or the layer's own).

        Every entry is a *distinct* buffer: the installs' scatters and the
        model's serving steps donate the cache (in-place update), so aliasing
        one zeros array across K/V/layers would leave dead buffers behind the
        first call. (The CPU backend honours donation too, on jax 0.9.0: a
        donated input reads ``is_deleted()`` there, so CPU-only tests see it.)
        """
        return [
            tuple(
                jnp.zeros((self.num_blocks, *t.block_shape), dtype=t.dtype)
                for t in self.layer_tensors(layer)
            )
            for layer in range(self.num_layers)
        ]


# ---------------------------------------------------------------------------
# Pure-XLA paths (work on any backend; also the semantic reference for tests).
# ---------------------------------------------------------------------------


@jax.jit
def gather_blocks_xla(cache: jax.Array, block_ids: jax.Array) -> jax.Array:
    """out[i] = cache[block_ids[i]]."""
    return jnp.take(cache, block_ids, axis=0)


@jax.jit
def scatter_blocks_xla(
    cache: jax.Array, block_ids: jax.Array, blocks: jax.Array
) -> jax.Array:
    """cache[block_ids[i]] = blocks[i]; returns the updated cache (donate the
    input under jit for in-place update)."""
    return cache.at[block_ids].set(blocks)


# ---------------------------------------------------------------------------
# Pallas kernels. Grid = one program per block; the scalar-prefetched id array
# feeds the BlockSpec index maps, so the pipeline DMAs cache[ids[i]] directly
# — the kernel body is a VMEM copy, and consecutive blocks double-buffer.
# ---------------------------------------------------------------------------


def _copy_kernel(ids_ref, in_ref, out_ref):
    del ids_ref
    out_ref[...] = in_ref[...]


def _scatter_kernel(ids_ref, blocks_ref, cache_ref, out_ref):
    # cache_ref is the aliased full cache (stays in HBM, never DMA'd); only
    # the ids-addressed output blocks are written.
    del ids_ref, cache_ref
    out_ref[...] = blocks_ref[...]


def _block_spec_shape(spec_shape):
    # One cache block per grid step: leading index 1, full trailing dims.
    return (1, *spec_shape[1:])


# A grid step holds a block in and a block out, each double-buffered. Mosaic's
# scoped VMEM is 16 MiB unless told otherwise, which four blocks of up to 4 MiB
# fit; a larger block (a 2,048-token page of ten 128-wide heads is 5 MiB) asks
# for its own limit, and every smaller one lowers the program it always did.
_SCOPED_VMEM = 16 << 20


def _copy_params(cache) -> dict:
    need = 4 * int(np.prod(cache.shape[1:])) * cache.dtype.itemsize
    if need <= _SCOPED_VMEM:
        return {}
    return {"compiler_params": pltpu.CompilerParams(vmem_limit_bytes=need + (4 << 20))}


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather_blocks_pallas(cache, block_ids, *, interpret):
    n = block_ids.shape[0]
    block = _block_spec_shape(cache.shape)
    rest = (0,) * (cache.ndim - 1)  # a block of any rank: whole trailing dims
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec(block, lambda i, ids: (ids[i], *rest)),
        ],
        out_specs=pl.BlockSpec(block, lambda i, ids: (i, *rest)),
    )
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, *cache.shape[1:]), cache.dtype),
        interpret=interpret,
        **_copy_params(cache),
    )(block_ids, cache)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def _scatter_blocks_pallas(cache, block_ids, blocks, *, interpret):
    n = block_ids.shape[0]
    block = _block_spec_shape(cache.shape)
    rest = (0,) * (cache.ndim - 1)  # a block of any rank: whole trailing dims
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec(block, lambda i, ids: (i, *rest)),
            pl.BlockSpec(memory_space=pl.ANY),  # aliased cache, not DMA'd
        ],
        out_specs=pl.BlockSpec(block, lambda i, ids: (ids[i], *rest)),
    )
    # Aliasing cache -> output makes this an in-place update: grid steps only
    # write the targeted blocks, everything else keeps its bytes. The alias
    # index counts the scalar-prefetch operand (ids=0, blocks=1, cache=2).
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
        **_copy_params(cache),
    )(block_ids, blocks, cache)


def _use_pallas() -> bool:
    """Whether the dispatchers of this package take their Pallas branch: on
    the chip, never elsewhere (the kernels run off it only in interpret
    mode, under test). The one copy: flash_prefill, chunk_attention,
    paged_attention and kv_quant ask this module, and a test that steers
    the dispatch patches this name."""
    return jax.default_backend() == "tpu"


def gather_blocks(cache: jax.Array, block_ids: jax.Array) -> jax.Array:
    """Gather cache blocks by dynamic id. Pallas on TPU, XLA elsewhere."""
    if _use_pallas():
        return _gather_blocks_pallas(cache, block_ids, interpret=False)
    return gather_blocks_xla(cache, block_ids)


def scatter_blocks(cache: jax.Array, block_ids: jax.Array, blocks: jax.Array) -> jax.Array:
    """Scatter blocks into the cache by dynamic id (in-place when donated)."""
    if _use_pallas():
        return _scatter_blocks_pallas(cache, block_ids, blocks, interpret=False)
    return scatter_blocks_xla(cache, block_ids, blocks)
