"""Paged KV slot pool: maps logical requests onto physical batch slots.

The FreeKV decode state is one pytree with a fixed batch dimension (the slot
count) — ``core/paging.py`` page tables, window rings, selection buffers and
per-row lengths. A jitted ``serve_step`` over that state never recompiles as
requests come and go; admission and completion are per-slot functional
updates:

  * ``insert(src_state, slot)`` splices a freshly prefilled B=1 state into a
    physical slot (prelude layers batch on axis 0, period-stacked pattern
    layers on axis 1, ``pos`` on axis 0 — see ``paging.slot_write_leaf``).
  * ``free(slot)`` returns the slot and marks it dirty; the reset to the
    empty template is LAZY (``flush_resets``, called by the scheduler right
    before a decode step) so a slot refilled at the same step boundary — the
    common case — pays one splice, not two. Slots that stay idle are reset
    once so their ring/page writes stay bounded until the next refill.

The slot index is a traced scalar, so one compiled insert serves every slot.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Set

import jax
import jax.numpy as jnp

from repro.core import paging
from repro.models.model import init_decode_state


def _splice(dst, src, slot, interpret=None, host_shardings=None):
    """``host_shardings`` (the ``dst`` tree, a NamedSharding at each
    host-resident leaf under a mesh, else None) lets the host-row DMA run
    per shard."""
    def put(group, axis):
        shardings = (host_shardings[group] if host_shardings is not None
                     else [jax.tree.map(lambda _: None, d) for d in dst[group]])
        return tuple(
            jax.tree.map(lambda a, b, sh: paging.slot_write_leaf(
                a, b, slot, axis=axis, interpret=interpret, sharding=sh),
                d, s, h)
            for d, s, h in zip(dst[group], src[group], shardings))

    out = dict(dst)
    out["prelude"] = put("prelude", 0)
    out["pattern"] = put("pattern", 1)
    out["pos"] = paging.slot_write_leaf(dst["pos"], src["pos"], slot, axis=0)
    # any extra top-level lane (e.g. the spec-decode draft_tab) batches on
    # axis 0, like pos
    for key in dst:
        if key not in ("prelude", "pattern", "pos"):
            out[key] = paging.slot_write_leaf(dst[key], src[key], slot, axis=0)
    return out


def _extract(state, slot):
    out = {
        "prelude": tuple(
            jax.tree.map(lambda a: paging.slot_read_leaf(a, slot, axis=0), d)
            for d in state["prelude"]),
        "pattern": tuple(
            jax.tree.map(lambda a: paging.slot_read_leaf(a, slot, axis=1), d)
            for d in state["pattern"]),
        "pos": paging.slot_read_leaf(state["pos"], slot, axis=0),
    }
    for key in state:
        if key not in ("prelude", "pattern", "pos"):
            out[key] = paging.slot_read_leaf(state[key], slot, axis=0)
    return out


class SlotPool:
    """Fixed-capacity pool of physical batch slots over one decode state.

    With a ``mesh`` (tensor-parallel serving), every state leaf is stored
    under the sharding ``sharding/rules.decode_state_shardings`` assigns —
    KV-head dim over 'model' for pools/summaries/rings/selection buffers —
    so the shard_map'ped decode step consumes its inputs without any
    resharding, and per-slot splices stay slot-local per shard. Under
    ``fkv.offload == 'host'`` the pool leaves are created in pinned_host
    memory, in the host word format (``core/offload.init_decode_state``)."""

    def __init__(self, cfg, fkv, num_slots: int, max_len: int,
                 state_dtype=jnp.float32, mesh=None):
        self.cfg, self.fkv = cfg, fkv
        self.num_slots = num_slots
        self.max_len = max_len
        self.state_dtype = state_dtype
        self.mesh = mesh

        def _mk_init(batch):
            fn = lambda: init_decode_state(cfg, fkv, batch, max_len,  # noqa: E731
                                           state_dtype)
            if mesh is None:
                return jax.jit(fn)
            from repro.sharding.rules import decode_state_shardings
            shardings = decode_state_shardings(cfg, mesh, jax.eval_shape(fn))
            return jax.jit(fn, out_shardings=shardings)

        self._init_full = _mk_init(num_slots)
        # the reset template stays in device memory even under the host
        # tier: a splice writes host rows by DMA from HBM
        self._template = _mk_init(1)()
        # the destination state is DONATED: a splice updates the pool in
        # place instead of copying every leaf (the pool dominates the state
        # footprint). The B=1 source (arg 1) is NOT donated — the reset
        # template is spliced in repeatedly. Callers reassign ``self.state``
        # immediately, so the consumed buffers are never read again.
        from repro.core.offload import is_host
        from repro.kernels.ops import resolve_interpret
        self._extract = jax.jit(_extract)
        self.state = self._fresh_state()
        host_shardings = None if mesh is None else {
            k: jax.tree.map(lambda a: a.sharding if is_host(a) else None,
                            self.state[k]) for k in ("prelude", "pattern")}
        self._splice = jax.jit(
            functools.partial(_splice, interpret=resolve_interpret(fkv),
                              host_shardings=host_shardings),
            donate_argnums=(0,))
        self._free: List[int] = list(range(num_slots - 1, -1, -1))
        self._dirty: Set[int] = set()
        self.owner: List[Optional[int]] = [None] * num_slots
        self.allocs = 0

    def _fresh_state(self):
        """The all-slots zero state; under ``fkv.offload == 'host'`` its pool
        leaves are created in host memory directly —
        sharding-preserving under a mesh (each shard's KV-head-group slice
        is host-resident on its own device)."""
        from repro.core.offload import init_decode_state as init_placed
        return init_placed(self._init_full, self.fkv, mesh=self.mesh,
                           cfg=self.cfg)

    # -- bookkeeping ---------------------------------------------------
    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def active_slots(self) -> List[int]:
        return [s for s, o in enumerate(self.owner) if o is not None]

    def alloc(self, owner_uid: int) -> int:
        slot = self._free.pop()
        assert self.owner[slot] is None, \
            f"slot {slot} already owned by request {self.owner[slot]}"
        self._dirty.discard(slot)       # insert() will overwrite every leaf
        self.owner[slot] = owner_uid
        self.allocs += 1
        return slot

    def free(self, slot: int):
        assert self.owner[slot] is not None, f"slot {slot} already free"
        self.owner[slot] = None
        self._free.append(slot)
        self._dirty.add(slot)

    def flush_resets(self):
        """Reset slots freed since the last flush that were not refilled —
        call before stepping so idle slots carry the empty template."""
        for slot in sorted(self._dirty):
            self.state = self._splice(self.state, self._template,
                                      jnp.int32(slot))
        self._dirty.clear()

    def pool_bytes(self) -> int:
        """Physical host-tier bytes (packed pool payload + quant scales)
        across every slot and layer — what the host actually holds."""
        from repro.core.offload import pool_bytes
        return pool_bytes(self.state)

    def pool_bytes_detail(self) -> dict:
        """Payload/scales/physical/dense breakdown of the pool footprint;
        ``ratio`` is the effective host-capacity multiplier the quantized
        tier buys (1.0 when kv_quant='none')."""
        from repro.quant import pool_bytes_detail
        return pool_bytes_detail(
            self.state, self.cfg.d_head,
            dense_itemsize=jnp.dtype(self.state_dtype).itemsize)

    # -- state surgery -------------------------------------------------
    def insert(self, src_state, slot: int):
        """Splice a B=1 prefilled decode state into physical slot ``slot``."""
        self.state = self._splice(self.state, src_state, jnp.int32(slot))

    def extract(self, slot: int):
        """Read one slot back out as a B=1 state (testing / migration)."""
        return self._extract(self.state, jnp.int32(slot))

    # -- preemption swap (scheduler priority preemption) -----------------
    def swap_out(self, slot: int):
        """Pull slot ``slot``'s entire decode state to host numpy and return
        it (the caller frees the slot separately).

        The swap unit is the slot's full B=1 pytree — paged pool (at its
        PACKED width under the quantized host tier: the int8/int4 payload and
        fp32 scales move as stored, never dequantized), page summaries, sink
        + window rings, selection buffers ``sel_k/sel_v/sel_idx``, ``qprev``,
        lengths and ``pos`` — so ``swap_in`` restores a bit-identical slot:
        mid-decode generation resumes exactly where it left off, including
        the staged speculative recall buffer the overlap pipeline carries
        across steps."""
        from repro.core.offload import swap_state_to_host
        return swap_state_to_host(self._extract(self.state, jnp.int32(slot)))

    def swap_in(self, host_state, slot: int):
        """Splice a ``swap_out`` host state back into physical slot ``slot``
        (allocated by the caller). Leaves upload at their stored dtypes —
        the packed pool representation round-trips exactly — and reuse the
        same compiled splice as ``insert`` (shapes match the template)."""
        self.state = self._splice(self.state,
                                  jax.tree.map(jnp.asarray, host_state),
                                  jnp.int32(slot))

    def reset_all(self):
        self.state = self._fresh_state()
        self._free = list(range(self.num_slots - 1, -1, -1))
        self._dirty = set()
        self.owner = [None] * self.num_slots
