"""What a block of the paged cache weighs, read off the caches the program built.

The harness knows nothing of a configuration's cache but this: ``caches`` is
a list with one entry per layer, each entry a tuple of tensors (of any
length: a K and a V, or a latent and a rope key, or one tensor), and every
tensor's leading axis is the block. A block of a layer's tensor is one value
in the store. From that alone come the bytes a block holds over all layers,
the values it puts in the store, the largest of them (the server's block
size) and their mean (a fetch takes every layer's values of a block, so a
count of fetched values times the mean is exact).

The configuration's ``serving`` states two of these numbers, because the
server is started and its pool sized before the program has built anything:
``kv_bytes_per_token`` and ``store_block_kib``. ``check`` holds the file to
what the program built, so a file cannot misstate its cache.
"""

import dataclasses
from typing import Dict, List, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class CacheGeometry:
    value_nbytes: Tuple[Tuple[int, ...], ...]  # per layer, per tensor: bytes a block

    @classmethod
    def of(cls, caches: Sequence[Sequence]) -> "CacheGeometry":
        """``caches`` as the program made them; a tensor needs ``nbytes``
        and ``shape`` only (a jax or a numpy array)."""
        return cls(tuple(tuple(t.nbytes // t.shape[0] for t in layer) for layer in caches))

    @property
    def _values(self) -> List[int]:
        return [n for layer in self.value_nbytes for n in layer]

    @property
    def block_nbytes(self) -> int:
        """One block, all layers, all tensors."""
        return sum(self._values)

    @property
    def values_per_block(self) -> int:
        """Store values (keys) one block puts in the store."""
        return len(self._values)

    @property
    def largest_value_nbytes(self) -> int:
        return max(self._values)

    @property
    def mean_value_nbytes(self) -> float:
        return self.block_nbytes / self.values_per_block

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
