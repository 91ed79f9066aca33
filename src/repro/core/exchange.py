"""Cross-partition exchange executors.

Every distributed primitive in the engine is written once against the
semantic contract

    transpose(x)[p, q, ...] == x[q, p, ...]      for x of shape [P, P, ...]

i.e. "partition q's block destined for partition p arrives at p, labelled q".
Two executors implement the contract:

  * LocalExchange — the whole [P, P, ...] array lives on one device and the
    exchange is literally an axis transpose.  Used by unit tests, examples,
    and CPU-only correctness runs: identical engine code, zero collectives.

  * SpmdExchange — the engine step runs inside `jax.shard_map` with the
    leading partition axis sharded one-partition-per-device; the exchange is
    `lax.all_to_all`.  Used by the multi-pod dry-run and real deployments.

This is the JAX analog of GraphX-on-Spark's shuffle layer (§4.1): the
engine never talks to the network directly, only to this interface — which is
what lets the identical mrTriplets/Pregel code be verified on 1 CPU device
and lowered onto a 512-chip mesh.

On-wire representation is delegated to the codec layer (`core/wire.py`,
DESIGN.md §2.1): `ship()` encodes each payload on the send side (per-block
scaled int8/fp8 quantization, lossless small-int packing, plain bf16
narrowing), moves the narrow payload plus its block scales through the
collective, and decodes on the receive side — both conversions behind
`optimization_barrier` so XLA cannot re-widen the collective.

Layering above this interface (who decides WHAT reaches `ship`): the
transport (`core/transport.py`, §2.1.1) decides how a routed buffer moves
(dense vs ragged-compacted), and the graph-resident view (`core/view.py`,
§3.1) decides which leaves and rows need to move at all — per-leaf dirty
tracking turns an operator chain's exchanges into deltas, so by the time a
buffer reaches this layer it is already the minimal routed set.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from . import transport as transport_mod
from . import wire as wire_mod
from .wire import WireCodec, make_codec

# the named scope over every collective SpmdExchange runs
COLLECTIVE_SCOPE = "graphx.collective"


class Exchange:
    """Executor interface. `p` is the number of graph partitions."""

    p: int

    def transpose(self, x: jnp.ndarray) -> jnp.ndarray:  # [P, P, ...] -> [P, P, ...]
        raise NotImplementedError

    def tree_transpose(self, tree):
        return jax.tree.map(self.transpose, tree)

    def ppermute(self, x: jnp.ndarray, shift: int) -> jnp.ndarray:
        """Rotate blocks around the partition ring: out[(i+shift) % P] gets
        partition i's block.  The primitive under `ring_transpose`."""
        raise NotImplementedError

    def ring_transpose(self, x: jnp.ndarray) -> jnp.ndarray:
        """The SAME contract as `transpose`, realised as P ring stages
        (DESIGN.md §2.1.2): stage d moves each partition's d-th diagonal
        block one hop of distance d.  Bit-identical output — pure data
        movement, no arithmetic — but where `transpose` is ONE monolithic
        all_to_all the scheduler must fence, the ring stages are P
        independent small collectives: each consumes only the send buffer
        and fills a disjoint slice of the result, so XLA's async collective
        scheduler can overlap stage d+1's wire time with compute that
        consumes stage d's block (the fused superstep sweep of the tile
        that already arrived).  Requires one partition per executor shard.
        """
        raise NotImplementedError

    def all_gather_rows(self, x: jnp.ndarray) -> jnp.ndarray:
        """Broadcast-lane collective (DESIGN.md §2.1.3): every partition
        contributes its local block [nl, B, ...] ONCE and receives all of
        them — out[l, q, ...] == x_global[q, ...], shape [nl, P, B, ...].
        One payload per source, delivered everywhere: the all-gather the
        high-replication mirror exchange lowers to."""
        raise NotImplementedError

    def psum(self, x: jnp.ndarray) -> jnp.ndarray:
        """Mesh-global sum of a per-executor quantity.  LocalExchange holds
        the whole array, so the local value IS global; SpmdExchange psums
        over the partition axis.  The transport layer's plan decisions
        (active fraction, overflow) go through this so they are uniform
        across the mesh — a device-divergent dense/ragged choice would give
        the collectives mismatched shapes."""
        return x

    def pmax(self, x: jnp.ndarray) -> jnp.ndarray:
        """Mesh-global maximum of a per-executor quantity (identity where
        the executor holds every partition, as `psum`)."""
        return x

    def home_rows(self, nl: int) -> jnp.ndarray:
        """[nl] int32 GLOBAL partition ids of this executor's local rows.
        LocalExchange holds every partition, so rows ARE global ids; inside
        shard_map a device's single row is its mesh position.  The receive
        side of the integrity check (DESIGN.md §6) salts its recomputed
        word with these, so a misrouted block cannot verify."""
        return jnp.arange(nl, dtype=jnp.int32)

    # Wire-format hook (DESIGN.md §2.1): the codec every `ship` routes
    # through.  Set via `with_wire(ex, codec)`.
    wire: WireCodec | None = None

    @property
    def codec(self) -> WireCodec | None:
        """The wire codec in effect (None = full-width f32 shipping)."""
        return self.wire

    def ship(self, x: jnp.ndarray, *, active: jnp.ndarray | None = None,
             bound: int | None = None, transport=None) -> jnp.ndarray:
        """transpose() through the wire codec and the selected transport.

        active: [nl, P, K] per-entry freshness flags (the superstep's changed
        mask routed onto this buffer) — stale entries are zero-substituted
        before quantization so they cannot pollute block scales or wrap an
        exact int cast; bound: static |value| bound for lossless integer
        narrowing (§2.3.1 id-valued convention); transport: a
        `core.transport` plan (None | "dense" | "ragged" | "auto" |
        TransportPolicy) deciding HOW the buffer moves — ragged plans
        compact the active entries per destination (§2.1.1), so stale
        positions come back as zeros rather than shipped values.

        Plain dtype narrowing (bf16) STAYS narrow on return — the mirror
        view stores the wire dtype and accumulation upcasts at the consumer:
        upcasting right after the collective would let XLA hoist the convert
        to the send side and run the collective wide again (measured on the
        PageRank cell's a2a; hence the barriers in wire.py).  Scaled and
        packed-int payloads decode back to their original dtype — dequant is
        a separately-shipped per-block exponent multiply, which XLA cannot
        commute across the collective."""
        tp = transport_mod.ragged_plan(transport, active)
        if tp is not None:
            recv, _, _ = transport_mod.ship_transport(
                self, x, active, bound=bound, policy=tp)
            return recv
        enc = wire_mod.encode_leaf(x, self.codec, bound=bound, active=active)
        if enc is None:
            return self.transpose(x)
        payload = self.transpose(enc.payload)
        scale = None if enc.scale is None else self.transpose(enc.scale)
        return wire_mod.decode_leaf(enc.kind, payload, scale, x, self.codec)

    def tree_ship(self, tree, *, active: jnp.ndarray | None = None,
                  bound: int | None = None, transport=None):
        tp = transport_mod.ragged_plan(transport, active)
        if tp is not None:
            recv, _, _ = transport_mod.ship_transport(
                self, tree, active, bound=bound, policy=tp)
            return recv
        return jax.tree.map(
            lambda x: self.ship(x, active=active, bound=bound), tree)


@dataclasses.dataclass(frozen=True)
class LocalExchange(Exchange):
    """Single-device executor: exchange is a transpose of the block matrix."""

    p: int
    wire: WireCodec | None = None

    def transpose(self, x: jnp.ndarray) -> jnp.ndarray:
        assert x.shape[0] == self.p and x.shape[1] == self.p, x.shape
        return jnp.swapaxes(x, 0, 1)

    def ppermute(self, x: jnp.ndarray, shift: int) -> jnp.ndarray:
        assert x.shape[0] == self.p, x.shape
        return jnp.roll(x, shift % self.p, axis=0)

    def ring_transpose(self, x: jnp.ndarray) -> jnp.ndarray:
        # stage-by-stage simulation of the ring schedule: at stage d the
        # receiver r gets sender (r-d) % p's block x[(r-d) % p, r] and files
        # it at out[r, (r-d) % p] — after p stages, out == transpose(x).
        assert x.shape[0] == self.p and x.shape[1] == self.p, x.shape
        p = self.p
        rows = jnp.arange(p)
        out = jnp.zeros_like(x)
        for d in range(p):
            src = (rows - d) % p
            out = out.at[rows, src].set(x[src, rows])
        return out

    def all_gather_rows(self, x: jnp.ndarray) -> jnp.ndarray:
        # the whole [P, B, ...] array is resident: every local row l simply
        # observes each source row q — a broadcast of the row axis.
        assert x.shape[0] == self.p, x.shape
        return jnp.broadcast_to(x[None], (self.p,) + x.shape)


@dataclasses.dataclass(frozen=True)
class SpmdExchange(Exchange):
    """shard_map executor: partition axis is a named mesh axis.

    Inside shard_map the global [P, P, ...] array arrives as a local block
    [P // n, P, ...] (leading axis sharded over `axis_name`, n devices).  The
    contract transpose is exactly `lax.all_to_all` splitting the *second*
    axis and concatenating on the first — the collective moves each
    [blk, blk, ...] tile x[q, p] to device p.

    Every collective it runs lies under the `graphx.collective` scope, so
    a device trace can tell the time the chips spend exchanging apart.
    """

    p: int
    axis_name: str = "parts"
    wire: WireCodec | None = None

    def transpose(self, x: jnp.ndarray) -> jnp.ndarray:
        # local x: [P_loc=1, P, ...].  Tiled all_to_all over axis 1: device p
        # sends tile q to device q and receives tile (q -> position q), i.e.
        # out[0, q] = x_global[q, p] — exactly the transpose contract.
        with jax.named_scope(COLLECTIVE_SCOPE):
            return jax.lax.all_to_all(
                x, self.axis_name, split_axis=1, concat_axis=1, tiled=True)

    def ppermute(self, x: jnp.ndarray, shift: int) -> jnp.ndarray:
        s = shift % self.p
        if s == 0:
            return x
        with jax.named_scope(COLLECTIVE_SCOPE):
            return jax.lax.ppermute(
                x, self.axis_name,
                [(i, (i + s) % self.p) for i in range(self.p)])

    def ring_transpose(self, x: jnp.ndarray) -> jnp.ndarray:
        # local x: [1, P, ...] (one partition per device — the ring schedule
        # keys block position off the device index).  Stage d: this device r
        # sends its column block x[:, (r+d) % p] a distance-d hop; the block
        # arriving here came from (r-d) % p and lands at that column of the
        # output.  Stage 0 is the local diagonal (no collective).  Each
        # stage reads only `x` and writes a disjoint output column, so the
        # P-1 ppermutes are mutually independent — the async-collective
        # property `transpose`'s single fused all_to_all cannot offer.
        p = self.p
        r = jax.lax.axis_index(self.axis_name)
        out = jnp.zeros_like(x)
        for d in range(p):
            blk = jax.lax.dynamic_slice_in_dim(x, (r + d) % p, 1, axis=1)
            if d:
                with jax.named_scope(COLLECTIVE_SCOPE):
                    blk = jax.lax.ppermute(
                        blk, self.axis_name,
                        [(i, (i + d) % p) for i in range(p)])
            out = jax.lax.dynamic_update_slice_in_dim(
                out, blk, (r - d + p) % p, axis=1)
        return out

    def all_gather_rows(self, x: jnp.ndarray) -> jnp.ndarray:
        # local x: [1, B, ...] (this device's block).  One tiled all-gather
        # over the partition axis — THE collective the broadcast lane
        # asserts on in the HLO (vs P point-to-point payloads) — then a
        # leading unit axis to restore the [nl, P, B, ...] local layout.
        assert x.shape[0] == 1, x.shape
        with jax.named_scope(COLLECTIVE_SCOPE):
            return jax.lax.all_gather(
                x, self.axis_name, axis=0, tiled=True)[None]

    def psum(self, x: jnp.ndarray) -> jnp.ndarray:
        with jax.named_scope(COLLECTIVE_SCOPE):
            return jax.lax.psum(x, self.axis_name)

    def pmax(self, x: jnp.ndarray) -> jnp.ndarray:
        with jax.named_scope(COLLECTIVE_SCOPE):
            return jax.lax.pmax(x, self.axis_name)

    def home_rows(self, nl: int) -> jnp.ndarray:
        base = jax.lax.axis_index(self.axis_name).astype(jnp.int32)
        return base * nl + jnp.arange(nl, dtype=jnp.int32)


def with_wire(ex: Exchange, codec, *, delta: bool | None = None,
              block: int | None = None,
              pack_ints: bool | None = None,
              resident: bool | None = None) -> Exchange:
    """Return a copy of `ex` shipping through the given wire codec.

    codec: a WireCodec, a registry name ("f32" | "bf16" | "int8" |
    "fp8_e4m3" | "fp8_e5m2"), or None to strip the codec.  Keyword overrides
    tweak the resolved codec (delta shipping, scale block size, int
    packing, narrow-RESIDENT mirrors — DESIGN.md §2.4)."""
    resolved = make_codec(codec, delta=delta, block=block,
                          pack_ints=pack_ints, resident=resident)
    return dataclasses.replace(ex, wire=resolved)  # type: ignore[arg-type]
