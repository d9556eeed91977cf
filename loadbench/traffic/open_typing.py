"""Open-loop typing traffic over many small rooms (crdt-benchmarks B2.2).

Each change inserts ONE character at a random position of its author's
view of the room's text: two wire ops (``ins`` + ``set``). The two peers
of a room type concurrently, as in B2 ("two users producing conflicts"):
a peer's view is the base text plus its own inserts, so its change
depends on the base and on its own previous change only.

Arrivals are a Poisson stream switched on and off in fixed cycles
(``burst.on_s`` on, ``burst.off_s`` off). Room popularity is Zipf with
``zipf_constant`` over a seed-drawn ranking of the rooms (YCSB's
zipfian). Every seed offers the same number of changes in every cycle,
the same multiset of inter-arrival gaps, and the same number of changes
to the room of each popularity rank; the seed only reorders the gaps and
the changes and draws which room holds each rank, the peers and the
positions, so the work is the same from seed to seed.
"""

from __future__ import annotations

import math

import numpy as np

from loadbench.population import LETTERS, Offers, seed_change


class OpenTyping:
    def __init__(self, config: dict, mix: dict, seed: int,
                 window_s: float):
        rng = np.random.default_rng([seed, 0x7157])
        self.rate = float(mix["rate_changes_per_s"])
        self.warmup_s = float(mix["warmup_s"])
        self.drain_s = float(mix["drain_s"])
        n_rooms = int(config["rooms"])
        n_peers = int(config["peers_per_room"])
        n_base = int(config["base_chars"])
        self.rooms = []
        self.peers = []
        for r in range(n_rooms):
            room = f"room-{r:05d}"
            obj = f"text-{r:05d}"
            base = f"base-{r:05d}"
            self.rooms.append({"room": room, "obj": obj, "base": base,
                               "seed": seed_change(base, obj, n_base, rng)})
            for j in range(n_peers):
                self.peers.append({"tid": f"{room}-p{j}", "room": room,
                                   "actor": f"r{r:05d}p{j}", "online": True,
                                   "room_index": r})
        self._schedule(rng, mix, n_rooms, n_peers, n_base, window_s)
        self.offers = Offers()
        self._next = 0
        self._t0 = None

    def _schedule(self, rng, mix, n_rooms, n_peers, n_base, window_s):
        """Due times (seconds from the window's first instant; warm-up
        ones are negative) and the changes, drawn from the seed."""
        segments = mix.get("rate_steps") or [
            [self.warmup_s + window_s, self.rate]]
        on_s = float(mix["burst"]["on_s"])
        off_s = float(mix["burst"]["off_s"])
        cycle = on_s + off_s
        due = []
        t0 = -self.warmup_s
        for seg_s, rate in segments:
            per_cycle = int(round(float(rate) * cycle))
            # exponential quantiles: the same gap multiset every cycle
            # and seed, in a seed-drawn order
            q = -np.log1p(-(np.arange(per_cycle) + 0.5) / per_cycle)
            for c in range(int(math.ceil(float(seg_s) / cycle))):
                gaps = rng.permutation(q)
                pos = np.cumsum(gaps) / (gaps.sum() * (1 + 1 / per_cycle))
                start = t0 + c * cycle
                if start >= t0 + float(seg_s):
                    break
                due.append(start + on_s * pos)
            t0 += float(seg_s)
        due = np.concatenate(due) if due else np.zeros(0)
        due = due[due < max(window_s, t0)]
        # Zipf popularity over a seed-drawn room ranking: the rank of
        # popularity r gets its expected share of the n changes (largest
        # remainders round), the same for every seed; the seed draws which
        # room holds each rank and the order of the changes
        n = len(due)
        ranks = np.arange(1, n_rooms + 1, dtype=np.float64)
        share = ranks ** -float(mix["zipf_constant"])
        want = n * share / share.sum()
        counts = np.floor(want).astype(np.int64)
        extra = np.argsort(-(want - counts), kind="stable")[:n - counts.sum()]
        counts[extra] += 1
        order = rng.permutation(n_rooms)
        rooms = order[rng.permutation(np.repeat(np.arange(n_rooms), counts))]
        peers = rooms * n_peers + rng.integers(0, n_peers, n)
        u = rng.random(n)
        letters = LETTERS[rng.integers(0, 26, n)].tolist()
        seqs: dict = {}
        self.schedule = []
        for i in range(n):
            p = int(peers[i])
            peer = self.peers[p]
            base = self.rooms[peer["room_index"]]["base"]
            obj = self.rooms[peer["room_index"]]["obj"]
            seq = seqs.get(p, 0) + 1
            seqs[p] = seq
            # the author knows _head, the base and its own inserts
            pick = int(u[i] * (n_base + seq))
            if pick == 0:
                parent = "_head"
            elif pick <= n_base:
                parent = f"{base}:{pick}"
            else:
                parent = f"{peer['actor']}:{pick}"
            elem = n_base + seq
            actor = peer["actor"]
            change = {"actor": actor, "seq": seq,
                      "deps": {base: 1} if seq == 1 else {},
                      "ops": [{"action": "ins", "obj": obj, "key": parent,
                               "elem": elem},
                              {"action": "set", "obj": obj,
                               "key": f"{actor}:{elem}",
                               "value": letters[i]}]}
            self.schedule.append((float(due[i]), p, change))

    # -- the harness's interface ------------------------------------------

    def max_room_inserts(self) -> int:
        """The most one-character inserts the schedule sends one room
        (warm-up and window)."""
        counts = np.bincount([self.peers[p]["room_index"]
                              for _due, p, _c in self.schedule],
                             minlength=len(self.rooms))
        return int(counts.max()) if len(counts) else 0

    def reveal(self, i: int) -> dict:
        """The clock the peer advertises when it opens its connection."""
        peer = self.peers[i]
        base = self.rooms[peer["room_index"]]["base"]
        return {"docId": peer["room"], "clock": {base: 1}}

    def start(self, now: float):
        """Fix the schedule's origin: due times are relative to the first
        instant of the window, ``warmup_s`` from now (warm-up offers fall
        before it)."""
        self._t0 = now + self.warmup_s

    def warm_done(self, now: float):
        """The window's first instant once the warm-up is over, else
        None."""
        return self._t0 if now >= self._t0 else None

    def inject(self, harness, now: float):
        sched, t0, clock = self.schedule, self._t0, harness.clock
        offers = self.offers
        while self._next < len(sched):
            due, p, change = sched[self._next]
            if t0 + due > now:
                break
            self._next += 1
            base = self.rooms[self.peers[p]["room_index"]]["base"]
            harness.send(p, {"docId": self.peers[p]["room"],
                             "clock": {base: 1,
                                       change["actor"]: change["seq"]},
                             "changes": [change]})
            offers.add(change, p, t0 + due, clock())

    def room_changes(self) -> dict:
        """Room index -> every change its document should hold: the seed,
        then every offered change of the room (offer order is causal
        order per author, and the authors are concurrent)."""
        out = {r: [room["seed"]] for r, room in enumerate(self.rooms)}
        for c, s in zip(self.offers.changes, self.offers.sender):
            out[self.peers[s]["room_index"]].append(c)
        return out


def make(config: dict, mix: dict, seed: int, window_s: float):
    return OpenTyping(config, mix, seed, window_s)
