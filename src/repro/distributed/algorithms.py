"""Explicit collective algorithms (shard_map + ppermute/psum).

The paper's Fig 5 compares Open MPI vs MPICH Allreduce variants (recursive
doubling / reduce-scatter-allgather / ring) by their traced communication
patterns.  We implement the same three algorithms explicitly so the tracer
can show their distinct collective signatures on the TPU mesh, and compare
them against XLA's built-in all-reduce.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _axis_size(axis_name):
    return jax.lax.axis_size(axis_name)


def ring_allreduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Textbook ring: n-1 reduce-scatter hops + n-1 all-gather hops, one
    1/n-payload neighbor ppermute per hop."""
    n = _axis_size(axis_name)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, -1)                     # local copy of each chunk

    # reduce-scatter phase: device i ends up owning the full sum of
    # chunk (i+1) mod n
    carry = jnp.take(chunks, idx, axis=0)
    for s in range(n - 1):
        with jax.named_scope("ring_rs_hop"):
            carry = jax.lax.ppermute(carry, axis_name, perm)
            carry = carry + jnp.take(chunks, jnp.mod(idx - s - 1, n), axis=0)
    owned = jnp.mod(idx + 1, n)

    # all-gather phase: circulate the reduced chunks
    out = jnp.zeros_like(chunks)
    out = jax.lax.dynamic_update_index_in_dim(out, carry, owned, 0)
    cur = carry
    for s in range(n - 1):
        with jax.named_scope("ring_ag_hop"):
            cur = jax.lax.ppermute(cur, axis_name, perm)
            src_owner = jnp.mod(idx - s - 1, n)
            chunk_id = jnp.mod(src_owner + 1, n)
            out = jax.lax.dynamic_update_index_in_dim(out, cur, chunk_id, 0)
    res = out.reshape(-1)
    if pad:
        res = res[:flat.size - pad]
    return res.reshape(x.shape)


def xla_allreduce(x, axis_name):
    """XLA's built-in all-reduce (ring/torus schedule chosen by XLA)."""
    return jax.lax.psum(x, axis_name)


def rsag_allreduce(x: jax.Array, axis_name: str) -> jax.Array:
    """reduce-scatter + all-gather via the dedicated collectives."""
    n = _axis_size(axis_name)
    if n == 1:
        return x
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    with jax.named_scope("rsag_rs"):
        scattered = jax.lax.psum_scatter(flat.reshape(n, -1), axis_name,
                                         scatter_dimension=0, tiled=False)
    with jax.named_scope("rsag_ag"):
        gathered = jax.lax.all_gather(scattered, axis_name, tiled=False)
    out = gathered.reshape(-1)
    if pad:
        out = out[:flat.size - pad]
    return out.reshape(x.shape)


def recursive_doubling_allreduce(x: jax.Array, axis_name: str) -> jax.Array:
    """log2(n) exchange rounds with partner at distance 2^k (full payload)."""
    n = _axis_size(axis_name)
    if n == 1:
        return x
    assert n & (n - 1) == 0, "recursive doubling needs power-of-two group"
    out = x
    for k in range(int(math.log2(n))):
        d = 1 << k
        perm = [(i, i ^ d) for i in range(n)]
        with jax.named_scope(f"recdbl_round{k}"):
            out = out + jax.lax.ppermute(out, axis_name, perm)
    return out


ALGORITHMS = {
    "xla": xla_allreduce,              # XLA's all-reduce (baseline)
    "ring": ring_allreduce,
    "rsag": rsag_allreduce,
    "recursive_doubling": recursive_doubling_allreduce,
}


def allreduce_fn(algorithm: str, mesh, axis_name: str = "data",
                 keep_specs: P = None):
    """shard_map-wrapped allreduce over one mesh axis."""
    fn = ALGORITHMS[algorithm]

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P(axis_name),
                       out_specs=P(axis_name), check_vma=False)
    def run(shard):
        return fn(shard, axis_name)

    return run
