"""What a block of the paged cache weighs, read off the caches the program built.

The harness knows nothing of a configuration's cache but this: ``caches`` is
a list with one entry per layer, each entry a tuple of tensors (of any
length: a K and a V, or a latent and a rope key, or one tensor), and every
tensor's leading axis is the block. A block of a layer's tensor is one value
in the store. From that alone come the bytes a block holds over all layers,
the values it puts in the store and the largest of them (the server's block
size). Every block WRITES every tensor, whatever a hit reads back.

What a hit of n blocks reads back is the one thing the caches cannot say, so
the configuration's file may: ``serving.hit_installs`` lists the tensors that
are checkpoints, ``{"layers": [...], "tensor": <index>, "last_blocks":
<count>}`` each. Such a tensor is installed in the hit's trailing ``count``
blocks only (1 for a recurrent state, which the last block's value replaces
whole; window / block_tokens for a sliding layer); a tensor no entry names is
installed in every block of the hit (``every_block``, and what a file without
the key says of all its tensors). From the policy come the values and bytes
a hit fetches and installs, and the (layer, tensor, block) triples in which
the installed bytes must be the saved ones.

The configuration's ``serving`` also states the geometry in numbers, because
the server is started and its pool sized before the program has built
anything: ``kv_bytes_per_token`` (real bytes a token), ``store_block_kib``
(the largest value one block of one layer puts in the store) and, for a cache
whose values are not all of that one size, ``store_unit_kib`` (the server's
allocation unit, a power of two; a value takes whole units) and
``store_values_kib`` (``[count, KiB]`` pairs over all layers of one block).
``store_layout`` reads them, ``pool_bytes_per_block`` says what a block takes
of the pool, and ``check`` holds the file to what the program built, so a
file cannot misstate its cache.
"""

import collections
import dataclasses
from typing import Dict, Iterator, List, Mapping, Optional, Sequence, Tuple

MIN_UNIT_KIB = 16  # the server refuses a smaller allocation unit
# The pool is the plan's working set over POOL_FILL, and 2 GiB: under the
# server's on-demand eviction threshold (its default ``on_demand_evict_min``),
# so that nothing is evicted.
POOL_FILL = 0.7
POOL_EVICTS_FROM = 0.8


def _whole(i) -> bool:
    return isinstance(i, int) and not isinstance(i, bool)


def pool_bytes_per_block(values_kib: Sequence[Sequence[int]], unit_kib: int) -> int:
    """Bytes of the server's pool one block takes: every value of
    ``values_kib`` (``[count, KiB]`` pairs) rounded up to whole units."""
    return 1024 * sum(count * -(-kib // unit_kib) * unit_kib for count, kib in values_kib)


def pool_gib(need_bytes: float) -> int:
    """The server's pool, in whole GiB, for a working set of ``need_bytes``
    of it: what ``run.py`` has always given, never under 2."""
    return max(2, int(need_bytes / POOL_FILL / 2**30) + 2)


@dataclasses.dataclass(frozen=True)
class StoreLayout:
    """What the file's ``serving`` says of the store, before anything is built."""

    unit_kib: int  # the server's ``--minimal-allocate-size``
    block_kib: int  # the largest value, or the unit where the file gives none and that is larger
    values_kib: Tuple[Tuple[int, int], ...]  # (count, KiB) over all layers of one block
    block_tokens: int

    @property
    def pool_bytes_per_block(self) -> int:
        return pool_bytes_per_block(self.values_kib, self.unit_kib)

    @property
    def pool_units_per_block(self) -> int:
        return self.pool_bytes_per_block // (self.unit_kib * 1024)

    @property
    def pool_bytes_per_token(self) -> float:
        """What ``traffic.store_bytes`` weighs a token at to size the pool."""
        return self.pool_bytes_per_block / self.block_tokens

    def value_nbytes(self) -> "collections.Counter[int]":
        """The multiset of value sizes, in bytes, that ``check`` compares."""
        return collections.Counter({kib * 1024: count for count, kib in self.values_kib})


def store_layout(serving: Dict) -> StoreLayout:
    """The unit and the values of a block as the file states them; raises
    ``ValueError`` with the numbers where they cannot start a server.

    Without ``store_unit_kib`` the unit is ``store_block_kib`` (never under
    16), so that must be a power of two. Without ``store_values_kib`` every
    value is of ``store_block_kib``, as many as ``kv_bytes_per_token x
    block_tokens`` holds: for a file whose cache is tensors of one size, both
    are what ``run.py`` computed before the keys existed."""
    block, unit = serving["store_block_kib"], serving.get("store_unit_kib")
    if not _whole(block) or block < 1:
        raise ValueError(f"serving.store_block_kib is {block!r}: whole KiB, 1 or more")
    power_of_two = lambda n: n & (n - 1) == 0
    if unit is None:
        unit = max(MIN_UNIT_KIB, block)
        if not power_of_two(unit):
            raise ValueError(
                f"serving.store_block_kib is {block}, which is no power of two, and the file "
                f"gives no serving.store_unit_kib: the server allocates in units of a power of "
                f"two KiB, so a cache whose largest value is {block} KiB has to name its unit"
            )
    elif not _whole(unit) or unit < MIN_UNIT_KIB or not power_of_two(unit) or unit > block:
        raise ValueError(
            f"serving.store_unit_kib is {unit!r} beside a serving.store_block_kib of {block}: "
            f"the unit is a power of two, at least {MIN_UNIT_KIB} and no larger than the "
            f"largest value"
        )
    values, bt = serving.get("store_values_kib"), serving["block_tokens"]
    if values is None:
        count, rest = divmod(serving["kv_bytes_per_token"] * bt, block * 1024)
        if rest or count < 1:
            raise ValueError(
                f"serving.kv_bytes_per_token x block_tokens is {serving['kv_bytes_per_token'] * bt} "
                f"bytes a block, no whole number of values of serving.store_block_kib ({block} "
                f"KiB): the values differ in size, so the file has to list them in "
                f"serving.store_values_kib"
            )
        values = [[count, block]]
    ok = isinstance(values, list) and values and all(
        isinstance(v, list) and len(v) == 2 and all(_whole(i) and i >= 1 for i in v) for v in values
    )
    if not ok or max(kib for _, kib in values) != block:
        raise ValueError(
            f"serving.store_values_kib is {values!r}: a list of [count, KiB] pairs over all "
            f"layers of one block, whole numbers, the largest KiB serving.store_block_kib ({block})"
        )
    merged = collections.Counter()
    for count, kib in values:
        merged[kib] += count
    return StoreLayout(unit, max(unit, block), tuple((merged[k], k) for k in sorted(merged)), bt)


@dataclasses.dataclass(frozen=True)
class CacheGeometry:
    value_nbytes: Tuple[Tuple[int, ...], ...]  # per layer, per tensor: bytes a block
    # Per layer, per tensor: the trailing blocks of a hit that are installed
    # (a checkpoint), or None for every block.
    last_blocks: Tuple[Tuple[Optional[int], ...], ...]

    @classmethod
    def of(cls, caches: Sequence[Sequence], hit_installs: Sequence[Dict] = ()) -> "CacheGeometry":
        """``caches`` as the program made them; a tensor needs ``nbytes``
        and ``shape`` only (a jax or a numpy array). ``hit_installs`` as the
        configuration's file has it; an entry that names a layer or a tensor
        the caches do not have raises ``ValueError`` with both shapes."""
        value_nbytes = tuple(tuple(t.nbytes // t.shape[0] for t in layer) for layer in caches)
        last = [[None] * len(layer) for layer in value_nbytes]
        built = f"the caches the program built have {len(last)} layers of {[len(l) for l in last]} tensors"
        for entry in hit_installs:
            layers, tensor, count = (entry.get(k) for k in ("layers", "tensor", "last_blocks"))
            if not isinstance(layers, list) or not _whole(count) or count < 1:
                raise ValueError(
                    f"serving.hit_installs: an entry is a list of layers, a tensor and a "
                    f"last_blocks of 1 or more, not {entry}"
                )
            for layer in layers:
                if not (_whole(layer) and _whole(tensor) and 0 <= layer < len(last)
                        and 0 <= tensor < len(last[layer])):
                    raise ValueError(
                        f"serving.hit_installs names tensor {tensor!r} of layer {layer!r} in the "
                        f"configuration's file, but {built}"
                    )
                if last[layer][tensor] is not None:
                    raise ValueError(f"serving.hit_installs names tensor {tensor} of layer {layer} twice")
                last[layer][tensor] = count
        return cls(value_nbytes, tuple(tuple(layer) for layer in last))

    @property
    def _values(self) -> List[int]:
        return [n for layer in self.value_nbytes for n in layer]

    @property
    def block_nbytes(self) -> int:
        """One block, all layers, all tensors: what a save writes."""
        return sum(self._values)

    @property
    def values_per_block(self) -> int:
        """Store values (keys) one block puts in the store."""
        return len(self._values)

    @property
    def largest_value_nbytes(self) -> int:
        return max(self._values)

    def _hit(self, blocks: int) -> Iterator[Tuple[int, int, int, int]]:
        """(layer, tensor, blocks of it a hit of ``blocks`` installs, bytes a block)."""
        for layer, tensors in enumerate(self.value_nbytes):
            for tensor, nbytes in enumerate(tensors):
                count = self.last_blocks[layer][tensor]
                yield layer, tensor, blocks if count is None else min(blocks, count), nbytes

    def compared(self, blocks: int) -> List[Tuple[int, int, int]]:
        """The (layer, tensor, block) triples a hit of ``blocks`` installs:
        every block of an ``every_block`` tensor, the trailing ones of a
        checkpoint."""
        return [
            (layer, tensor, block)
            for layer, tensor, held, _ in self._hit(blocks)
            for block in range(blocks - held, blocks)
        ]

    def installed_blocks(self, blocks: int) -> List[List[range]]:
        """Per layer and tensor, the blocks of a hit of ``blocks`` that
        ``compared`` names: what the check reads back from the device."""
        out: List[List[range]] = [[] for _ in self.value_nbytes]
        for layer, _, held, _ in self._hit(blocks):
            out[layer].append(range(blocks - held, blocks))
        return out

    def fetched_values(self, blocks: int) -> int:
        """Store values a hit of ``blocks`` fetches."""
        return sum(held for _, _, held, _ in self._hit(blocks))

    def installed_nbytes(self, blocks: int) -> int:
        """Bytes a hit of ``blocks`` puts on the device."""
        return sum(held * nbytes for _, _, held, nbytes in self._hit(blocks))

    def fetched_nbytes(self, values: int, blocks: int) -> float:
        """Bytes of the ``values`` store values the program counted as
        fetched for a hit of ``blocks``, at the mean size of the values the
        policy names for such a hit: exact where it fetched just those
        (``fetched_values(blocks)`` of them) or, as without a checkpoint,
        whole layers of them."""
        blocks = max(blocks, 1)
        return values * self.installed_nbytes(blocks) / self.fetched_values(blocks)

    def check(self, serving: Dict) -> None:
        """Raises ``ValueError`` with both numbers, or both lists, where the
        file's ``serving`` disagrees with the caches."""
        per_token = self.block_nbytes / serving["block_tokens"]
        if per_token != serving["kv_bytes_per_token"]:
            raise ValueError(
                f"serving.kv_bytes_per_token is {serving['kv_bytes_per_token']} in the "
                f"configuration's file, but the caches the program built hold {per_token:g} "
                f"bytes a token ({self.block_nbytes} a block of {serving['block_tokens']} tokens)"
            )
        stated = serving["store_block_kib"] * 1024
        if stated != self.largest_value_nbytes:
            raise ValueError(
                f"serving.store_block_kib is {serving['store_block_kib']} ({stated:g} bytes) in "
                f"the configuration's file, but the largest value a block of one layer puts in "
                f"the store is {self.largest_value_nbytes} bytes"
            )
        stated, built = store_layout(serving).value_nbytes(), collections.Counter(self._values)
        if stated != built:
            as_list = lambda c: [[n, size // 1024 if size % 1024 == 0 else size / 1024] for size, n in sorted(c.items())]
            raise ValueError(
                f"serving.store_values_kib is {as_list(stated)} ([count, KiB] pairs; without the "
                f"key, all of store_block_kib) in the configuration's file, but a block of the "
                f"caches the program built puts {as_list(built)} in the store"
            )


def hit_mismatch(
    installed: Sequence[Sequence], saved: Mapping[str, Sequence[Sequence[bytes]]],
    chains: Sequence[str], geometry: CacheGeometry,
) -> Optional[str]:
    """The full hit's byte comparison, as a pure function: ``None`` where
    every block the policy says a hit of ``len(chains)`` blocks installs
    holds the bytes that were saved with that block's chain hash, else a
    sentence naming the first that does not. ``installed[layer][tensor]`` is
    the blocks ``geometry.installed_blocks(n)`` names of that tensor read
    back from the device, in that order (all n of an ``every_block`` tensor,
    the trailing ones of a checkpoint: its earlier blocks are not looked at,
    the program need not have installed them); ``saved[chain][layer][tensor]``
    the bytes the save of that chain was handed."""
    n = len(chains)
    shape = [len(layer) for layer in installed]
    if shape != [len(layer) for layer in geometry.value_nbytes]:
        return f"read back {len(shape)} layers of {shape} tensors, not the caches' own"
    for block, chain in enumerate(chains):
        if chain not in saved:
            return f"block {block} of {n}: no save of its chain of hashes was seen"
    for layer, tensors in enumerate(geometry.installed_blocks(n)):
        for tensor, blocks in enumerate(tensors):
            held = installed[layer][tensor]
            if len(held) != len(blocks):
                return (
                    f"layer {layer} tensor {tensor}: read back {len(held)} blocks, a hit of {n} "
                    f"installs {len(blocks)}"
                )
            for at, block in enumerate(blocks):
                if held[at].tobytes() != saved[chains[block]][layer][tensor]:
                    return f"layer {layer} tensor {tensor} block {block} of {n} is not the bytes that were saved"
    return None
