"""Recurrent state beside paged KV: the second kind of cache.

A model with recurrent layers (`ModelConfig.recurrent`) keeps, for every
sequence, a state that is not pages — whatever parts `hybrid.zero_state`
gives its layers: per Mamba-2 layer the SSM state `[H, P, N]` float32
and the last K-1 inputs of the causal conv; per retention layer
(models/retention.py) the feature-map state and its normaliser, 34 MB a
layer a sequence at published widths; per scanned RUN of Mamba-1 layers
(models/mamba1.py) ONE leaf a part, `[rows, run_layers, ...]`, rows
ahead of layers so that a slot's whole state is one index here; per
gated short-convolution layer (models/shortconv.py) two rows of the
model's width, 8 KB, the whole of it. Pages
can be truncated to any prefix and shared by reference; a state is valid
at exactly ONE position — the number of tokens it has consumed — and can
only be copied whole. This module owns both halves of that, and knows
the parts only as small ones a program gathers by row
(`hybrid.ROW_PARTS`) and ones updated in place on the slot array
(`hybrid.SLOT_PARTS`), whatever leaves the tree has under each — a leaf
a layer or a leaf a run of layers, the row is the first axis of all:

- **Slot states** — one row a knight slot (`state[part][l][row]`; the
  last row is scratch, where pad rows of a
  batch land). The tree is donated through the engine's step programs
  beside the page pools. What the expert layers counted in a dispatch
  (experts hit, assignments to held experts, expert-layer steps) comes
  back as an output of its own, never donated: `note_counts` queues it
  and folds it into host ints on the dispatching thread once its
  program has been read. Nothing off that thread touches the device.
- **The snapshot store** — `snapshot_bytes` of device memory holding
  states at PAGE BOUNDARIES, keyed by the content of the prefix they
  consumed (a hash chain over whole pages, so any slot — own, sibling or
  another session's — whose prompt starts with those pages can start
  from it). Snapshots are taken where the chunked scan yields them for
  one more small product: the last page boundary a prefill chunk or a
  ragged join crosses — and, for a prompt whose pages were cached and
  for which no state stood anywhere (a new session behind the preamble
  every session opens with), the end of that cached span, once: the
  boundary the next such prompt starts from. They are bound to the radix node of their page
  when the slot commits (prefix_cache.insert) and dropped with that
  node; unbound ones age out LRU under the byte budget.

`plan()` is the reuse plan for one admission row: the longest prefix
for which pages AND a state exist — (i) the slot's own state when the
prompt extends exactly what it consumed, (ii) else the deepest snapshot
at a page boundary inside the matched pages, (iii) else zero. The
caller lowers the row's KV offset to that position (attention layers
re-write their few pages from there) and the state is copied in before
the first dispatch. A model whose layers are ALL recurrent has an empty
pool tree: its pages are ids that hold no bytes, and everything above
reads them as it does for every model.

**The leader pass hands a state on** (kvcache.share_prefixes, the
deferred pass of a scheduled admission): the span a request's rows have
in common is scanned ONCE, by the leader; the laggards block, and start
from the state the leader left at the last page boundary at or under
the span's end. The seams are the ones above and know no layer kind:
`hand_over` says at admission whether the store can hold that one
state pinned; `expect` marks the boundary for the leader's slot (the
first of its runs that crosses it leaves its one snapshot there,
`capture_slot`) and pins the snapshot — no capture and no radix node
evicts it — until the last laggard has planned or the request is
dropped (`unpin`); a laggard's `plan` runs when it unblocks, behind the
leader's capture in program order, and finds the snapshot by its key
like any other. Where that state cannot be had — an admission that is
not deferred, no boundary ahead of the leader, a store full of pins, a
boundary the leader's run gave to an earlier one — the rows scan the
span themselves as they did before, counted (`share_declined` beside
`share_handed`).

What a restore wrote into slot rows and what the programs' captures
wrote into the store are counted in bytes (`copy_bytes`, one writer:
`_note_copy`): at 206 MB a state they are device work an admission
waits for.

A model with `layer_kinds` and NO recurrent layer (`axk1`: attention,
MLP and expert layers) runs the same step programs with an EMPTY state
tree: its store holds no bytes and no snapshots, every position of its
pages is a place to continue from (`plan` hands the pages' own frontier
back), and what is left of this module for it is the expert counters.

Single-writer like the pool: every caller holds the engine's serve lock.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict, deque
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import telemetry
from .models import hybrid
from .models.common import ModelConfig

CONTINUE, SNAPSHOT, ZERO = "continue", "snapshot", "zero"
MOE_COUNTS = hybrid.MOE_COUNTS
# What a model with a seam counts (HybridStateStore.seam): a `segment`
# span carries each for the programs it covers, describe()["seam"] the
# lifetime totals, `roundtable_seam_<name>_total` the series.
SEAM_COUNTS = ("lower_tokens", "upper_rows", "memory_rows",
               "shared_pool_positions")


def _chain(prev: bytes, block: list[int]) -> bytes:
    return hashlib.blake2b(prev + np.asarray(block, np.int32).tobytes(),
                           digest_size=16).digest()


def page_keys(tokens: list[int], page_size: int, upto: int) -> list[bytes]:
    """keys[j] identifies tokens[:(j+1)*page_size], for every whole page
    at or below `upto` tokens."""
    out, key = [], b""
    for j in range(min(upto, len(tokens)) // page_size):
        key = _chain(key, tokens[j * page_size:(j + 1) * page_size])
        out.append(key)
    return out


class HybridStateStore:
    def __init__(self, cfg: ModelConfig, num_slots: int, page_size: int,
                 snapshot_bytes: int, engine: str = "engine",
                 dtype=jnp.bfloat16):
        # `dtype`: the engine's, which a part kept in the activations'
        # dtype takes (hybrid.zero_state).
        self.cfg = cfg
        self.dtype = dtype
        self.engine = engine
        self.page_size = page_size
        self.num_slots = num_slots
        self.scratch_row = num_slots
        self.bytes_per_state = hybrid.state_bytes_per_sequence(cfg, dtype)
        self.budget_bytes = int(snapshot_bytes) if cfg.recurrent else 0
        self.capacity = (self.budget_bytes // self.bytes_per_state
                         if cfg.recurrent else 0)
        self.scratch_snap = self.capacity
        self._alloc()
        self._row_of: dict[str, int] = {}
        self._consumed: dict[str, Optional[list[int]]] = {}
        self._keys: dict[str, list[bytes]] = {}
        self._snap: "OrderedDict[bytes, int]" = OrderedDict()
        self._free_snaps = list(range(self.capacity - 1, -1, -1))
        self._counts_pending: "deque[jax.Array]" = deque()
        self._moe_total = [0] * len(MOE_COUNTS)
        self._moe_seen = [0] * len(MOE_COUNTS)
        self.hits = self.misses = self.evictions = 0
        self.snapshots_taken = 0
        self.continued_tokens = self.reused_tokens = 0
        self.rescanned_tokens = 0
        # The leader pass: laggards that started from the state a leader
        # handed on, and those that scanned the span themselves.
        self.share_handed = self.share_declined = 0
        self.copy_bytes = {"restore": 0, "capture": 0}
        # Tokens x Mamba-1 layers the join programs scanned (pads left
        # out; a decode loop's steps advance states without the scan),
        # and tokens x short-convolution layers they ran.
        self.scan_tokens = 0
        self.conv_tokens = 0
        # A model whose upper layers keep nothing (`ModelConfig.
        # last_token_from`): what its JOIN programs ran on either side of
        # the seam, and what the layers that own no pages read of the
        # pool they share (`note_join`, `note_shared_reads`).
        self.seam = dict.fromkeys(SEAM_COUNTS, 0)

        @partial(jax.jit, donate_argnums=(0,))
        def restore(state, snaps, dst_rows, src_snaps, zero, n):
            # state[dst] = zero ? 0 : snaps[src], over whatever parts the
            # tree has. Small parts in one scatter: unused lanes copy
            # the scratch snapshot onto the scratch row. Large parts a
            # lane at a time, the first `n` lanes only, in place: a
            # gather of every lane would stand beside the state.
            out = {part: [
                a.at[dst_rows].set(jnp.where(
                    zero.reshape((-1,) + (1,) * (a.ndim - 1)),
                    0.0, s[src_snaps]))
                for a, s in zip(state[part], snaps[part])]
                for part in state if part in hybrid.ROW_PARTS}
            slot_parts = [p for p in state if p in hybrid.SLOT_PARTS]
            large = [a for part in slot_parts for a in state[part]]
            held = [s for part in slot_parts for s in snaps[part]]

            def lane(i, arrays):
                return [jax.lax.dynamic_update_index_in_dim(
                    a, jnp.where(zero[i], 0.0,
                                 jax.lax.dynamic_index_in_dim(
                                     s, src_snaps[i], 0, keepdims=False)),
                    dst_rows[i], 0) for a, s in zip(arrays, held)]

            if large:
                large = jax.lax.fori_loop(0, n, lane, large)
            it = iter(large)
            for part in slot_parts:
                out[part] = [next(it) for _ in state[part]]
            return out

        self._restore = restore
        # Per slot: the page-aligned end of a span the pages held and no
        # state did (plan), until a run of the slot crosses it.
        self._shared_to: dict[str, int] = {}
        # Per leader slot: the boundary its laggards wait at (expect);
        # per key: the laggards a snapshot is pinned for.
        self._hand_at: dict[str, int] = {}
        self._pins: dict[bytes, int] = {}

    def _alloc(self) -> None:
        self.state: dict[str, Any] = hybrid.zero_state(
            self.cfg, self.num_slots + 1, self.dtype)
        self.snaps = hybrid.zero_state(self.cfg, self.capacity + 1,
                                       self.dtype)

    # --- device trees ---------------------------------------------------

    def commit_state(self, state: dict) -> None:
        self.state = state

    def commit_snaps(self, snaps: dict) -> None:
        self.snaps = snaps

    def revive_if_dead(self) -> bool:
        """A failed donated dispatch may have consumed either tree:
        reallocate both and forget every host record (every next
        admission then starts from zero, which is always right)."""
        leaves = (jax.tree_util.tree_leaves(self.state)
                  + jax.tree_util.tree_leaves(self.snaps))
        if not any(x.is_deleted() for x in leaves):
            return False
        self._alloc()
        self._consumed.clear()
        self._snap.clear()
        self._free_snaps = list(range(self.capacity - 1, -1, -1))
        self._counts_pending.clear()
        return True

    def hbm_bytes(self) -> int:
        return sum(x.size * x.dtype.itemsize for x in
                   jax.tree_util.tree_leaves(self.state)
                   + jax.tree_util.tree_leaves(self.snaps))

    # --- rows -----------------------------------------------------------

    def row_of(self, name: str, live: Optional[set] = None) -> int:
        """The state row of slot `name`; a new name takes a free row,
        reclaiming the rows of names the pool no longer holds."""
        row = self._row_of.get(name)
        if row is not None:
            return row
        used = set(self._row_of.values())
        if len(used) >= self.num_slots:
            for old in [n for n in self._row_of
                        if live is not None and n not in live]:
                self.forget(old)
            used = set(self._row_of.values())
        if len(used) >= self.num_slots:
            raise RuntimeError(
                f"hybrid state: all {self.num_slots} state rows belong "
                "to live slots — raise num_slots")
        row = next(i for i in range(self.num_slots) if i not in used)
        self._row_of[name] = row
        self._consumed[name] = None
        return row

    def rows_for(self, names: list[str], width: int,
                 live: Optional[set] = None) -> np.ndarray:
        rows = np.full((width,), self.scratch_row, np.int32)
        for i, n in enumerate(names):
            rows[i] = self.row_of(n, live)
        return rows

    def names_by_row(self) -> dict[int, str]:
        return {row: name for name, row in self._row_of.items()}

    def forget(self, name: str) -> None:
        for table in (self._row_of, self._consumed, self._keys,
                      self._shared_to, self._hand_at):
            table.pop(name, None)

    def forget_all(self) -> None:
        for name in list(self._row_of):
            self.forget(name)

    # --- the reuse plan -------------------------------------------------

    def plan(self, name: str, tokens: list[int],
             kv_matched: int) -> tuple[int, str, Optional[int]]:
        """-> (start, source, snapshot index): where slot `name`'s state
        can stand for prompt `tokens`, given pages for tokens[:kv_matched].
        Also records the prompt's page keys, so captures of this
        admission can be keyed. Leaves the slot marked in flight (no continuation until
        its next commit). Without recurrent state the pages' frontier
        IS the place to continue from."""
        if not self.cfg.recurrent:
            return kv_matched, CONTINUE, None
        cap = min(kv_matched, len(tokens) - 1)
        keys = page_keys(tokens, self.page_size, len(tokens))
        self._keys[name] = keys
        best, source, snap = 0, ZERO, None
        for j in range(cap // self.page_size - 1, -1, -1):
            idx = self._snap.get(keys[j])
            if idx is not None:
                self._snap.move_to_end(keys[j])
                best, source, snap = (j + 1) * self.page_size, SNAPSHOT, idx
                break
        own = self._consumed.get(name)
        if (own is not None and best < len(own) <= cap
                and tokens[:len(own)] == own):
            best, source, snap = len(own), CONTINUE, None
        self._consumed[name] = None
        # Pages matched and no state at all: another sequence shares
        # that span (a preamble every session opens with), and the scan
        # from zero is about to cross its end — the boundary the next
        # such prompt needs (capture_slot takes it once).
        self._shared_to[name] = (cap // self.page_size * self.page_size
                                 if source == ZERO else 0)
        self._hand_at.pop(name, None)   # an older admission's
        if source == ZERO:
            self.misses += 1
        else:
            self.hits += 1
        if source == CONTINUE:
            self.continued_tokens += best
        else:
            self.reused_tokens += best
        self.rescanned_tokens += max(kv_matched - best, 0)
        telemetry.inc("roundtable_state_admissions_total",
                      engine=self.engine, source=source)
        telemetry.inc("roundtable_state_prompt_tokens_total",
                      len(tokens), engine=self.engine)
        telemetry.inc("roundtable_state_rescanned_tokens_total",
                      max(kv_matched - best, 0), engine=self.engine)
        return best, source, snap

    def attach(self, plans: list[tuple[str, str, Optional[int]]],
               live: Optional[set] = None) -> None:
        """Bring each planned row's state to its start: copy the
        snapshot in, or zero it; a continuing row is left alone. One
        program of one shape, whatever the mix."""
        todo = [(n, src, idx) for n, src, idx in plans if src != CONTINUE]
        for n, _s, _i in plans:
            self.row_of(n, live)
        if not todo:
            return
        for k in range(0, len(todo), self.num_slots):
            part = todo[k:k + self.num_slots]
            dst = np.full((self.num_slots,), self.scratch_row, np.int32)
            src = np.full((self.num_slots,), self.scratch_snap, np.int32)
            zero = np.zeros((self.num_slots,), bool)
            for i, (n, source, idx) in enumerate(part):
                dst[i] = self._row_of[n]
                if source == SNAPSHOT:
                    src[i] = idx
                else:
                    zero[i] = True
            self.state = self._restore(
                self.state, self.snaps, jnp.asarray(dst),
                jnp.asarray(src), jnp.asarray(zero),
                jnp.int32(len(part)))
        self._note_copy("restore", len(todo))

    def _note_copy(self, cause: str, states: int) -> None:
        """`states` whole states written: into slot rows by a restore,
        or into the store by the programs' captures."""
        n = states * self.bytes_per_state
        self.copy_bytes[cause] += n
        telemetry.inc("roundtable_state_copy_bytes_total", n,
                      engine=self.engine, cause=cause)

    def note_scan(self, tokens: int) -> None:
        """A join dispatch (a prologue chunk, a ragged step) fed its
        runs `tokens` tokens: what the model's Mamba-1 layers scanned,
        and what its short-convolution layers ran (re-scanned tokens
        among them)."""
        n = tokens * len(self.cfg.mamba1_layers)
        if n:
            self.scan_tokens += n
            telemetry.inc("roundtable_mamba1_scan_tokens_total", n,
                          engine=self.engine)
        n = tokens * len(self.cfg.shortconv_layers)
        if n:
            self.conv_tokens += n
            telemetry.inc("roundtable_shortconv_tokens_total", n,
                          engine=self.engine)

    def note_join(self, tokens: int, rows: int) -> None:
        """A join dispatch (a prologue chunk, a ragged step) of a model
        with a seam: `tokens` tokens went through the layers below it,
        `rows` rows — each sequence's last token — through the layers
        above, and as many rows of the memory were carried to them."""
        if self.cfg.last_token_from is None:
            return
        self._note_seam(lower_tokens=tokens, upper_rows=rows, memory_rows=(
            rows if self.cfg.memory_layer is not None else 0))

    def note_shared_reads(self, positions: int) -> None:
        """Positions x cross layers read from the pool of the attention
        layer below them, by layers that own no pages."""
        if self.cfg.cross_layers:
            self._note_seam(shared_pool_positions=positions
                            * len(self.cfg.cross_layers))

    def _note_seam(self, **counts: int) -> None:
        for name, n in counts.items():
            if n:
                self.seam[name] += n
                telemetry.inc(f"roundtable_seam_{name}_total", n,
                              engine=self.engine)

    def on_commit(self, name: str, tokens: list[int], exact: bool) -> None:
        """The slot committed `tokens`. `exact`: its state has consumed
        exactly those (a row that ended on a sampled eos consumed one
        more: no continuation from it)."""
        if name in self._row_of:
            self._consumed[name] = list(tokens) if exact else None

    # --- the leader pass ------------------------------------------------

    def hand_over(self, tokens: list[int], lo: int,
                  hi: int) -> Optional[tuple[bytes, int]]:
        """-> (key, boundary) of the state a leader whose pages stand at
        `lo` can hand the laggards of its admission: the one after the
        last page boundary at or under `hi`, the end of the span they
        have in common. None when there is none to hand — the leader
        has passed every such boundary — or none left to pin."""
        at = hi // self.page_size * self.page_size
        if at <= lo or not self.capacity:
            return None
        key = page_keys(tokens, self.page_size, at)[-1]
        if key not in self._pins and len(self._pins) >= self.capacity:
            return None
        return key, at

    def expect(self, leader: str, key: bytes, at: int,
               laggards: int) -> None:
        """Slot `leader` is about to scan across `hand_over`'s boundary
        and `laggards` blocked rows will start from its state there:
        the first of its runs that crosses it leaves its one snapshot
        there (capture_slot), pinned — as one that stands there already
        is — until each has planned or been dropped (`unpin`)."""
        self._hand_at[leader] = at
        self._pins[key] = self._pins.get(key, 0) + laggards
        if key in self._snap:
            self._snap.move_to_end(key)

    def unpin(self, key: bytes, laggards: int = 1) -> None:
        """`laggards` rows no longer wait for the snapshot: with the
        last of them it is the store's to evict again, in LRU order."""
        left = self._pins.get(key, 0) - laggards
        if left > 0:
            self._pins[key] = left
        else:
            self._pins.pop(key, None)

    def note_handed(self) -> None:
        """A laggard started from the state its leader handed on."""
        self.share_handed += 1
        telemetry.inc("roundtable_state_share_handed_total",
                      engine=self.engine)

    def note_declined(self, laggards: int, reason: str) -> None:
        """`laggards` rows scan a span they share with a leader
        themselves, from the deepest snapshot each finds."""
        self.share_declined += laggards
        telemetry.inc("roundtable_state_share_declined_total", laggards,
                      engine=self.engine, reason=reason)

    # --- snapshots ------------------------------------------------------

    def capture_slot(self, name: str, start: int, n_tokens: int
                     ) -> tuple[int, int, Optional[bytes]]:
        """For a dispatch that feeds slot `name` tokens [start, start +
        n): -> (cap_len, snapshot index, key) — after how many of them
        the state stands at the last page boundary the run crosses (or
        at the end of a span `plan` found held by the pages and by no
        state, or at the boundary `expect` marked, the once it is
        crossed), and where the program stores it; (0, scratch, None)
        when there is none, it is already held, every state held is
        pinned, or the prompt is unknown. A dispatch that fails drops
        the keys it reserved (`drop`, unwritten)."""
        ps = self.page_size
        end = start + n_tokens
        b = end // ps * ps
        keys = self._keys.get(name)
        # A boundary the slot owes comes before the last one: the end of
        # a span re-scanned from zero though its pages were held (plan),
        # then the one its laggards wait at (expect) — the first of them
        # this run crosses and no state stands at yet (its siblings,
        # behind it in the same admission, take the last one as ever).
        # Nothing is struck off here: the next run starts beyond it, and
        # a dispatch that failed and is issued again owes it again.
        for at in sorted((self._shared_to.get(name, 0),
                          self._hand_at.get(name, 0))):
            if (start < at <= end and keys is not None
                    and at // ps <= len(keys)
                    and keys[at // ps - 1] not in self._snap):
                b = at
                break
        if (self.capacity == 0 or b <= start or keys is None
                or b // ps > len(keys) or name.startswith("__warmup_")):
            return 0, self.scratch_snap, None
        key = keys[b // ps - 1]
        if key in self._snap:
            self._snap.move_to_end(key)
            return 0, self.scratch_snap, None
        if not self._free_snaps and not self._evict_lru():
            return 0, self.scratch_snap, None   # every state is pinned
        idx = self._free_snaps.pop()
        self._snap[key] = idx
        self.snapshots_taken += 1
        telemetry.inc("roundtable_state_snapshots_total",
                      engine=self.engine)
        self._note_copy("capture", 1)
        self._publish()
        return b - start, idx, key

    def _evict_lru(self) -> bool:
        """Free the oldest snapshot no laggard waits for; False when
        every one is pinned."""
        key = next((k for k in self._snap if k not in self._pins), None)
        if key is None:
            return False
        self._free_snaps.append(self._snap.pop(key))
        self.evictions += 1
        telemetry.inc("roundtable_state_snapshot_evictions_total",
                      engine=self.engine)
        return True

    def drop(self, key: Optional[bytes], unwritten: bool = False) -> None:
        """The radix node this snapshot was bound to is gone — a pinned
        one stays, unbound, for the laggards that wait for it — or
        (`unwritten`) the program that was to write it failed: it goes
        whoever waits."""
        if key in self._pins and not unwritten:
            return
        idx = self._snap.pop(key, None) if key is not None else None
        if idx is not None:
            self._free_snaps.append(idx)
            self.evictions += 1
            telemetry.inc("roundtable_state_snapshot_evictions_total",
                          engine=self.engine)
            self._publish()

    def drop_all_snapshots(self) -> None:
        for key in list(self._snap):
            self.drop(key)

    def bind_nodes(self, name: str, nodes: list) -> None:
        """prefix_cache.insert hands over the radix path of a committed
        slot: the snapshot of each page goes onto that page's node, and
        is evicted with it."""
        keys = self._keys.get(name) or []
        for node, key in zip(nodes, keys):
            if key in self._snap and getattr(node, "snap", None) is None:
                node.snap = key

    def holds(self, tokens: list[int], upto: int) -> bool:
        keys = page_keys(tokens, self.page_size, upto)
        return bool(keys) and keys[-1] in self._snap

    def snapshot_bytes(self) -> int:
        return len(self._snap) * self.bytes_per_state

    def _publish(self) -> None:
        telemetry.set_gauge("roundtable_state_snapshots", len(self._snap),
                            engine=self.engine)
        telemetry.set_gauge("roundtable_state_snapshot_bytes",
                            self.snapshot_bytes(), engine=self.engine)

    # --- counters -------------------------------------------------------

    def note_counts(self, counts: jax.Array, pipelined: bool) -> None:
        """Queue a dispatch's expert counts (int32 [5], an output of the
        program just issued). What was queued before is folded first —
        all of it, or all but the newest when this dispatch is a decode
        segment issued before the last one is read (the pipeline is one
        deep), so the fold never waits on a program in flight."""
        self.fold_counts(keep=1 if pipelined else 0)
        self._counts_pending.append(counts)

    def fold_counts(self, keep: int = 0) -> None:
        """Add queued counts to the host totals, oldest first, leaving
        the newest `keep`. Dispatching thread only (the serve lock)."""
        while len(self._counts_pending) > keep:
            try:
                got = np.asarray(self._counts_pending.popleft())
            except Exception:  # noqa: BLE001 — its program failed: the
                continue       # segment's own read has said so already
            for i, name in enumerate(MOE_COUNTS):
                self._moe_total[i] += int(got[i])
                telemetry.inc(f"roundtable_moe_{name}_total", int(got[i]),
                              engine=self.engine)

    def moe_totals(self) -> dict[str, int]:
        """Lifetime totals as folded so far. Host ints: any thread."""
        return dict(zip(MOE_COUNTS, self._moe_total))

    def moe_delta(self) -> dict[str, int]:
        """What was folded since the last call."""
        now = list(self._moe_total)
        delta = {name: new - old for name, new, old in
                 zip(MOE_COUNTS, now, self._moe_seen)}
        self._moe_seen = now
        return delta

    def describe(self) -> dict[str, Any]:
        return {
            "slots": len(self._row_of), "slot_rows": self.num_slots,
            "bytes_per_state": self.bytes_per_state,
            "snapshots": len(self._snap),
            "snapshot_capacity": self.capacity,
            "bytes": self.snapshot_bytes(), "budget": self.budget_bytes,
            "hits": self.hits, "misses": self.misses,
            "evictions": self.evictions,
            "snapshots_taken": self.snapshots_taken,
            "continued_tokens": self.continued_tokens,
            "reused_tokens": self.reused_tokens,
            "rescanned_tokens": self.rescanned_tokens,
            "share_handed": self.share_handed,
            "share_declined": self.share_declined,
            "restore_bytes": self.copy_bytes["restore"],
            "capture_bytes": self.copy_bytes["capture"],
        }
