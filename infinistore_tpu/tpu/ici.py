"""ICI fast path: intra-pod KV-block transfer between devices of one SPMD mesh.

The reference has exactly one transport — client socket to server socket over
the NIC. On TPU pods there is a second, much faster interconnect: ICI. When
the producer (prefill) and consumer (decode) of a KV block live on devices of
the same jitted mesh program — e.g. interleaved prefill/decode in one engine,
or a disaggregated engine pair launched as one SPMD job — blocks can move
HBM->HBM over ICI with XLA collectives, skipping host staging and DCN
entirely. The store API degrades gracefully: callers use this path when a
mesh is shared, and fall back to the DCN client (lib.InfinityConnection)
when it is not (SURVEY.md §7 hard part 4).

Implementation: shard_map over the transfer axis + lax.ppermute — the
canonical JAX way to express point-to-point device moves; XLA lowers it to
direct ICI sends with no host involvement.
"""

import functools
from typing import List, Sequence, Tuple

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _ppermute_fn(axis_name: str, perm: Tuple[Tuple[int, int], ...]):
    def fn(x):
        return jax.lax.ppermute(x, axis_name, perm)

    return fn


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis_name", "perm")
)
def _permute_sharded(blocks, *, mesh, axis_name, perm):
    spec = P(axis_name)
    return shard_map(
        _ppermute_fn(axis_name, perm),
        mesh=mesh,
        in_specs=spec,
        out_specs=spec,
    )(blocks)


class IciBlockTransfer:
    """Point-to-point KV-block moves across one mesh axis.

    `perm` is a list of (src_index, dst_index) pairs along `axis_name` —
    typically [(prefill_idx, decode_idx)] for a disaggregated pair. Data on
    devices not named as a destination comes back zeroed (ppermute
    semantics), so callers scatter only the destination shard's blocks.

    Every jitted transfer program is built once per (op, src, dst) and
    cached; an input already laid out with the transfer sharding is used
    as-is (no per-call reshard)."""

    def __init__(self, mesh: Mesh, axis_name: str, perm: Sequence[Tuple[int, int]]):
        self.mesh = mesh
        self.axis_name = axis_name
        self.axis_size = mesh.shape[axis_name]
        self.perm = tuple((int(s), int(d)) for s, d in perm)
        for s, d in self.perm:
            self._check_index(s, "perm src")
            self._check_index(d, "perm dst")
        self.sharding = NamedSharding(mesh, P(axis_name))
        self._jit_cache = {}
        # Dispatches of a compiled transfer program (one per host->device
        # launch). The whole point of the fused paths is to keep this at 1
        # per logical handoff; tests pin it.
        self.launches = 0

    def _check_index(self, i: int, what: str):
        """Out-of-range shard indices otherwise surface as an IndexError
        deep inside jit tracing (a 1-device mesh meeting a perm built for
        8) — validate at the API boundary instead."""
        if not 0 <= int(i) < self.axis_size:
            raise ValueError(
                f"{what} index {i} out of range for mesh axis "
                f"'{self.axis_name}' of size {self.axis_size}"
            )

    def _cached(self, key, build):
        fn = self._jit_cache.get(key)
        if fn is None:
            fn = build()
            self._jit_cache[key] = fn
        return fn

    def _ensure_sharded(self, arr: jax.Array) -> jax.Array:
        """Reshard only when needed: the hot path hands in caches that
        already live with the transfer sharding, and a full-cache reshard
        per call would swamp the transfer itself."""
        sh = getattr(arr, "sharding", None)
        if sh is not None and sh.is_equivalent_to(self.sharding, arr.ndim):
            return arr
        return jax.device_put(arr, self.sharding)

    def transfer(self, blocks_by_device: jax.Array) -> jax.Array:
        """blocks_by_device: [axis_size, n_blocks, *block_shape] sharded (or
        shardable) over axis 0. Returns the same shape with row dst holding
        what row src sent."""
        blocks = self._ensure_sharded(blocks_by_device)
        self.launches += 1
        return _permute_sharded(
            blocks, mesh=self.mesh, axis_name=self.axis_name, perm=self.perm
        )

    def send_blocks(
        self, cache: jax.Array, block_ids, src: int, dst: int
    ) -> jax.Array:
        """Convenience: gather `block_ids` from the per-device paged `cache`
        ([axis_size, num_blocks, ...], sharded over axis 0) on shard `src` and
        deliver them to shard `dst`. Returns [n, *block_shape] living on the
        dst device's shard row."""
        self._check_index(src, "src")
        self._check_index(dst, "dst")
        ids = jax.numpy.asarray(block_ids, dtype=jax.numpy.int32)
        mesh, axis = self.mesh, self.axis_name

        def build():
            perm = ((int(src), int(dst)),)

            def step(local_cache, local_ids):
                # Every shard gathers its own ids (SPMD; ids are replicated
                # via P()), only src's payload survives the permute.
                blocks = jax.numpy.take(local_cache[0], local_ids, axis=0)
                return jax.lax.ppermute(blocks[None], axis, perm)

            return jax.jit(
                shard_map(step, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(axis))
            )

        fn = self._cached(("send", int(src), int(dst)), build)
        self.launches += 1
        return fn(self._ensure_sharded(cache), ids)

    def handoff_blocks(
        self, cache: jax.Array, src_ids, dst_ids, src: int, dst: int
    ) -> jax.Array:
        """The full disagg handoff in ONE SPMD program: gather `src_ids`
        from shard `src`, move them HBM->HBM over ICI, scatter at `dst_ids`
        into shard `dst`'s pages. `cache`: [axis_size, num_blocks, *block],
        sharded over axis 0; it is donated — on TPU the update is in-place
        and only the moved blocks' bytes cross the interconnect."""
        self._check_index(src, "src")
        self._check_index(dst, "dst")
        s_ids = jax.numpy.asarray(src_ids, dtype=jax.numpy.int32)
        d_ids = jax.numpy.asarray(dst_ids, dtype=jax.numpy.int32)
        mesh, axis = self.mesh, self.axis_name

        def build():
            perm = ((int(src), int(dst)),)

            def step(local_cache, sids, dids):
                blocks = jax.numpy.take(local_cache[0], sids, axis=0)
                moved = jax.lax.ppermute(blocks[None], axis, perm)[0]
                updated = local_cache[0].at[dids].set(moved)
                is_dst = jax.lax.axis_index(axis) == dst
                return jax.numpy.where(is_dst, updated, local_cache[0])[None]

            return jax.jit(
                shard_map(
                    step, mesh=mesh, in_specs=(P(axis), P(), P()), out_specs=P(axis)
                ),
                donate_argnums=(0,),
            )

        fn = self._cached(("handoff", int(src), int(dst)), build)
        self.launches += 1
        return fn(self._ensure_sharded(cache), s_ids, d_ids)

    def handoff_kv(
        self, k_cache: jax.Array, v_cache: jax.Array, src_ids, dst_ids,
        src: int, dst: int
    ) -> Tuple[jax.Array, jax.Array]:
        """One layer's K and V handoff fused into a single SPMD program —
        one collective launch per layer instead of two on the
        latency-critical prefill->decode path. Both caches are donated."""
        self._check_index(src, "src")
        self._check_index(dst, "dst")
        s_ids = jax.numpy.asarray(src_ids, dtype=jax.numpy.int32)
        d_ids = jax.numpy.asarray(dst_ids, dtype=jax.numpy.int32)
        mesh, axis = self.mesh, self.axis_name

        def build():
            perm = ((int(src), int(dst)),)

            def one(local, sids, dids):
                blocks = jax.numpy.take(local[0], sids, axis=0)
                moved = jax.lax.ppermute(blocks[None], axis, perm)[0]
                updated = local[0].at[dids].set(moved)
                is_dst = jax.lax.axis_index(axis) == dst
                return jax.numpy.where(is_dst, updated, local[0])[None]

            def step(k_local, v_local, sids, dids):
                return one(k_local, sids, dids), one(v_local, sids, dids)

            return jax.jit(
                shard_map(
                    step, mesh=mesh,
                    in_specs=(P(axis), P(axis), P(), P()),
                    out_specs=(P(axis), P(axis)),
                ),
                donate_argnums=(0, 1),
            )

        fn = self._cached(("handoff_kv", int(src), int(dst)), build)
        self.launches += 1
        return fn(
            self._ensure_sharded(k_cache), self._ensure_sharded(v_cache), s_ids, d_ids
        )

    def handoff_layers(
        self, caches, src_ids, dst_ids, src: int, dst: int
    ) -> List[Tuple[jax.Array, jax.Array]]:
        """ALL layers' K+V handoff in one SPMD program with ONE collective.

        ``caches`` is the engine's full paged cache: a list of per-layer
        (K, V) arrays, each [axis_size, num_blocks, *block] sharded over the
        transfer axis. The per-layer path (`handoff_kv` in a Python loop)
        costs L sequential dispatch round-trips on the latency-critical
        prefill->decode handoff — the exact per-layer latency the reference's
        streaming design exists to hide (reference docs/source/design.rst:54-63).
        Here the gathered blocks of all 2L caches are stacked into a single
        [2L, n, *block] tensor, moved with one ppermute, and scattered back —
        one launch, one ICI transfer, still only the moved blocks' bytes on
        the wire. All caches are donated (updates are in-place in HBM).

        Requires uniform per-layer cache shape/dtype (true for every model
        family here; stacking is what buys the single collective).
        """
        L = len(caches)
        if L == 0:
            return []
        flat = [c for kv in caches for c in kv]
        shape, dtype = flat[0].shape, flat[0].dtype
        for c in flat:
            if c.shape != shape or c.dtype != dtype:
                raise ValueError(
                    "handoff_layers needs uniform per-layer cache shape/dtype; "
                    f"got {c.shape}/{c.dtype} vs {shape}/{dtype}"
                )
        self._check_index(src, "src")
        self._check_index(dst, "dst")
        s_ids = jax.numpy.asarray(src_ids, dtype=jax.numpy.int32)
        d_ids = jax.numpy.asarray(dst_ids, dtype=jax.numpy.int32)
        mesh, axis = self.mesh, self.axis_name

        def build():
            perm = ((int(src), int(dst)),)

            def step(sids, dids, *locals_):
                # One gather per cache, ONE ppermute for the stack of all of
                # them, then per-cache scatter. locals_[i]: [1, num_blocks, *block].
                gathered = jax.numpy.stack(
                    [jax.numpy.take(c[0], sids, axis=0) for c in locals_]
                )  # [2L, n, *block]
                moved = jax.lax.ppermute(gathered[None], axis, perm)[0]
                is_dst = jax.lax.axis_index(axis) == dst
                outs = []
                for i, c in enumerate(locals_):
                    updated = c[0].at[dids].set(moved[i])
                    outs.append(jax.numpy.where(is_dst, updated, c[0])[None])
                return tuple(outs)

            in_specs = (P(), P()) + tuple(P(axis) for _ in range(2 * L))
            out_specs = tuple(P(axis) for _ in range(2 * L))
            return jax.jit(
                shard_map(step, mesh=mesh, in_specs=in_specs, out_specs=out_specs),
                donate_argnums=tuple(range(2, 2 + 2 * L)),
            )

        fn = self._cached(("handoff_layers", L, int(src), int(dst)), build)
        sharded = [self._ensure_sharded(c) for c in flat]
        self.launches += 1
        outs = fn(s_ids, d_ids, *sharded)
        return [(outs[2 * i], outs[2 * i + 1]) for i in range(L)]


def mesh_from_devices(devices: List = None, axis_name: str = "store") -> Mesh:
    """A 1-D mesh over all local devices (helper for tests/examples)."""
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.array(devices), (axis_name,))
