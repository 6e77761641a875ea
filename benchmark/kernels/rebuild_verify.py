"""Least HBM bytes of the fused rebuild+verify kernel (ops/fused.py): every
surviving shard read once with its expected digests, every rebuilt shard
written once with one validity flag per survivor. Padding of a batch and
intermediate hash state are the implementation's own and do not count."""
from __future__ import annotations


def item_bytes(k: int, shard_bytes: int, chunk_bytes: int,
               rebuilt: int) -> int:
    """One block's rebuild: k survivors in, ``rebuilt`` shards out."""
    chunks = -(-shard_bytes // chunk_bytes)
    return k * shard_bytes + k * chunks * 32 + rebuilt * shard_bytes + k


def mean_item_bytes(geom: dict, object_bytes: int, rebuilt: int) -> float:
    """Mean over the blocks of one object: full blocks and a shorter last
    one, each an item of its own."""
    k, block = geom["data"], geom["block_bytes"]
    chunk = geom["bitrot_chunk_bytes"]
    sizes = [min(block, object_bytes - off)
             for off in range(0, object_bytes, block)]
    return sum(item_bytes(k, -(-n // k), chunk, rebuilt)
               for n in sizes) / len(sizes)
