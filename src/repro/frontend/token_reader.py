"""DPU-side token reader (paper §4.4).

"A background token reader continuously polls the ring buffer for generated
tokens. Each cycle, it issues one RDMA read to refresh cached slot metadata,
then compares each active slot's generation count with its local state to
detect new output. To minimize TTFT, new slots go to an *urgent slot* list
scanned first ... Adaptive polling bounds per-token latency while limiting
RDMA traffic."

Here a poll cycle = one bulk device_get of (slot_state, generated) + arena
rows for slots with new tokens. Adaptive polling: the interval halves when a
poll finds tokens and doubles (up to a cap) when idle.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

import numpy as np

from repro.core import ring_buffer as rb


class TokenReader:
    def __init__(self, num_slots: int, *, min_interval: float = 0.0,
                 max_interval: float = 0.01,
                 on_token: Optional[Callable[[int, int, int], None]] = None):
        self.num_slots = num_slots
        self.read_counts = np.zeros(num_slots, np.int64)  # local gen counts
        self.urgent: List[int] = []       # newly submitted slots, scan first
        self.on_token = on_token or (lambda slot, idx, tok: None)
        self.min_interval = min_interval
        self.max_interval = max_interval
        self.interval = min_interval
        self.polls = 0
        self.tokens_read = 0

    def mark_urgent(self, slot: int) -> None:
        self.urgent.append(slot)
        self.read_counts[slot] = 0

    def poll(self, slot_states: np.ndarray, generated: np.ndarray,
             output_arena: np.ndarray):
        """One poll cycle. Returns (new_tokens {slot: [tok,...]},
        completed [slot,...])."""
        self.polls += 1
        new_tokens: Dict[int, List[int]] = {}
        completed: List[int] = []

        order = self.urgent + [s for s in range(self.num_slots)
                               if s not in self.urgent]
        found = False
        for s in order:
            st = slot_states[s]
            # PREFILLING (mixed-phase chunked prefill) is scanned like the
            # decode states, but its generation count stays 0 until the
            # chunk cursor completes — the first token can never surface
            # (or be committed downstream) off a partially prefilled slot.
            # CANCELLED joins the scan set so a timed-out request's partial
            # output still streams; PREEMPTED/OFFLOADED are read like the
            # decode states (their tokens-so-far must not strand while the
            # slot waits for offload/restore); FAULTED likewise — a
            # quarantined request's tokens-so-far drain before release.
            if st not in (rb.DECODE_PROCESSING, rb.DECODE_PAUSED,
                          rb.DECODE_COMPLETED, rb.PREFILL_PROCESSING,
                          rb.PREFILLING, rb.CANCELLED, rb.PREEMPTED,
                          rb.OFFLOADED, rb.FAULTED):
                continue
            have = int(self.read_counts[s])
            avail = int(generated[s])
            if avail > have:
                toks = output_arena[s, have:avail].tolist()
                new_tokens[s] = toks
                for i, t in enumerate(toks):
                    self.on_token(s, have + i, t)
                self.read_counts[s] = avail
                self.tokens_read += avail - have
                found = True
            # terminal states complete once their output is drained — the
            # frontend maps CANCELLED to timed_out/preempted status and
            # FAULTED to "faulted"
            if st in (rb.DECODE_COMPLETED, rb.CANCELLED, rb.FAULTED) \
                    and avail <= self.read_counts[s]:
                completed.append(s)
                if s in self.urgent:
                    self.urgent.remove(s)
        # drained urgent slots that produced their first token leave the list
        self.urgent = [s for s in self.urgent if self.read_counts[s] == 0]

        # adaptive polling interval
        if found:
            self.interval = self.min_interval
        else:
            self.interval = min(self.max_interval,
                                max(self.interval * 2, 1e-4))
        return new_tokens, completed
