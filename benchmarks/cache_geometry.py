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

The configuration's ``serving`` also states two numbers of the geometry,
because the server is started and its pool sized before the program has built
anything: ``kv_bytes_per_token`` and ``store_block_kib``. ``check`` holds the
file to what the program built, so a file cannot misstate its cache.
"""

import dataclasses
from typing import Dict, Iterator, List, Mapping, Optional, Sequence, Tuple


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
        whole = lambda i: isinstance(i, int) and not isinstance(i, bool)
        for entry in hit_installs:
            layers, tensor, count = (entry.get(k) for k in ("layers", "tensor", "last_blocks"))
            if not isinstance(layers, list) or not whole(count) or count < 1:
                raise ValueError(
                    f"serving.hit_installs: an entry is a list of layers, a tensor and a "
                    f"last_blocks of 1 or more, not {entry}"
                )
            for layer in layers:
                if not (whole(layer) and whole(tensor) and 0 <= layer < len(last)
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
        """Raises ``ValueError`` with both numbers where the file's
        ``serving`` disagrees with the caches."""
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


def hit_mismatch(
    installed: Sequence[Sequence], saved: Mapping[str, Sequence[Sequence[bytes]]],
    chains: Sequence[str], geometry: CacheGeometry,
) -> Optional[str]:
    """The full hit's byte comparison, as a pure function: ``None`` where
    every block the policy says a hit of ``len(chains)`` blocks installs
    holds the bytes that were saved with that block's chain hash, else a
    sentence naming the first that does not. ``installed[layer][tensor]`` is
    the hit's own blocks of that tensor read back from the device, in the
    order of ``chains``; ``saved[chain][layer][tensor]`` the bytes the save
    of that chain was handed. A checkpoint's blocks before its trailing ones
    are not looked at: the program need not have installed them."""
    n = len(chains)
    shape = [len(layer) for layer in installed]
    if shape != [len(layer) for layer in geometry.value_nbytes]:
        return f"read back {len(shape)} layers of {shape} tensors, not the caches' own"
    for block, chain in enumerate(chains):
        if chain not in saved:
            return f"block {block} of {n}: no save of its chain of hashes was seen"
    for layer, tensor, block in geometry.compared(n):
        held = installed[layer][tensor]
        if len(held) != n:
            return f"layer {layer} tensor {tensor}: read back {len(held)} blocks of a hit of {n}"
        if held[block].tobytes() != saved[chains[block]][layer][tensor]:
            return f"layer {layer} tensor {tensor} block {block} of {n} is not the bytes that were saved"
    return None
