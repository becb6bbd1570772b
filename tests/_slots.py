"""The one driver of beam_search's slot kernels that tests share:
zero arrays, the page table, and the pack / step / unpack loop.

With no ``pages`` the state is built over the arena an engine with no
arena option gets (config.resolve_arena_pages: every slot at full
length), and slot ``i`` owns pages ``[i * b_max, (i + 1) * b_max)`` —
no admission can wait.  With ``pages`` the caller hands each pack the
page ids a ``PageArena`` gave it.
"""

import numpy as np

from textsummarization_on_flink_tpu.config import (
    resolve_arena_pages,
    resolve_enc_block,
)
from textsummarization_on_flink_tpu.decode import beam_search


def _direct(site, fn, *args, **kw):
    return fn(*args)


class Slots:
    """`slots` resident slots at `hps`, shaped like `arrays` (a
    [B, T_enc] encoder arrays dict).  ``call(site, fn, *args, key=...)``
    runs each kernel: tests that count compiles pass one that goes
    through the compile ledger."""

    def __init__(self, params, hps, arrays, slots, pages=None,
                 call=_direct):
        self.params, self.hps, self.slots = params, hps, slots
        self._call = call
        self.block = resolve_enc_block(hps)
        self.b_max = -(-hps.max_enc_steps // self.block)
        self.pages = (resolve_arena_pages(hps, slots) if pages is None
                      else pages)
        self._own_rows = pages is None
        # every unused entry points at the scratch page (index `pages`)
        self.table = np.full((slots, self.b_max), self.pages, np.int32)
        zero = {k: np.zeros((slots,) + v.shape[1:], v.dtype)
                for k, v in arrays.items()}
        self.state = call("decode/init_slots_jit",
                          beam_search.init_slots_jit, params, hps, zero,
                          self.pages)

    def prefill(self, one, key=""):
        return self._call("decode/prefill_jit", beam_search.prefill_jit,
                          self.params, self.hps, one, key=key)

    def pack(self, slot, pre, ids=None):
        """Admit a PrefillState (or a [1, bucket] arrays dict, prefilled
        here) into `slot` on page ids `ids` (default: the slot's own
        full-length pages of the default arena)."""
        if isinstance(pre, dict):
            pre = self.prefill(pre)
        if ids is None:
            assert self._own_rows, "a sized arena needs the page ids"
            ids = np.arange(slot * self.b_max, (slot + 1) * self.b_max)
        row = np.full(self.b_max, self.pages, np.int32)
        row[:len(ids)] = ids
        self.table[slot] = row
        self.state = self._call(
            "decode/pack_slot_jit", beam_search.pack_slot_jit,
            self.params, self.hps, self.state, slot, pre, row)

    def step(self, active, chunk):
        """One chunk; returns the finished mask as numpy."""
        self.state, fin = self._call(
            "decode/step_slots_jit", beam_search.step_slots_jit,
            self.params, self.hps, self.state, np.asarray(active, bool),
            self.table, chunk)
        return np.asarray(fin)

    def unpack(self, slot):
        return self._call(
            "decode/unpack_slot_jit", beam_search.unpack_slot_jit,
            self.hps, self.state, slot, self.table[slot])

    def drive(self, active=None, chunk=3, max_chunks=16):
        """Step until every active slot finishes; returns ({slot:
        BeamSearchOutput}, chunks run).  A retired slot's row goes back
        to scratch, as the engine's does."""
        active = (np.ones(self.slots, bool) if active is None
                  else np.array(active, bool))
        done = {}
        for n in range(1, max_chunks + 1):
            for s in np.nonzero(self.step(active, chunk))[0]:
                done[int(s)] = self.unpack(int(s))
                active[s] = False
                self.table[s] = self.pages
            if not active.any():
                return done, n
        raise AssertionError("slots never finished")
