"""From a profiler trace to device busy time, idle gaps and collective
exposure.

`extract` reads the ``.xplane.pb`` the JAX profiler wrote into a plain,
small form.  Per chip (each ``/device:TPU:<n>`` plane) its "XLA Ops"
line holds nested events: a ``while`` loop contains the operations of
its body.  The plain form keeps the outermost operations (their union is
the chip's busy time), the self time of every operation name (its time
less its children's), the collective operations of the "XLA Ops" and
"Async XLA Ops" lines, and the intervals in which some innermost
non-collective operation ran, where they meet a collective.  Of the
host it keeps the benchmark's own annotations (``bench.*``), which
bound the traced window.  `Timeline` does the arithmetic on the plain
form, so the tests check it on a small recorded trace.
"""
from __future__ import annotations

import bisect
import gzip
import json
import re
from dataclasses import dataclass
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
# collectives are found by HLO op kind, the start of the op's name
# (``all-reduce.3``, ``all-reduce-start.1``, ``all-gather-done``...)
COLLECTIVE = re.compile(r"(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|send|recv)")
# ... or a fusion whose called computation is one
CALLS_COLLECTIVE = re.compile(r"calls=%?(all-reduce|all-gather|"
                              r"reduce-scatter|collective-permute|all-to-all)")
HOST_PREFIX = "bench."


LAYOUT = re.compile(r"\{[^{}]*\}")


def short(name: str) -> str:
    """``%fusion.3 = bf16[8,128]{1,0} fusion(...)`` -> ``fusion.3
    bf16[8,128]``: the op and its result's type (at most 64 letters of
    it), which says what an anonymous fusion works on."""
    head, _, rest = name.partition(" = ")
    head = head.lstrip("%")
    if not rest:
        return head
    rest = LAYOUT.sub("", rest)
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        rtype = rest[:i + 1]
    else:
        rtype = rest.split(" ", 1)[0]
    return f"{head} {rtype[:64]}"


def is_collective(name: str) -> bool:
    """``name``: an op's whole trace name, or its short name."""
    return bool(COLLECTIVE.match(short(name))
                or CALLS_COLLECTIVE.search(name))


def reduce_line(events) -> dict:
    """One chip's nested "XLA Ops" events (in start order, as
    ``(name, start, end)``) -> outermost ops, self time per name,
    collectives, and merged innermost non-collective intervals."""
    top, coll, leaves = [], [], []
    self_ns: dict = {}
    stack = []   # [end, name, start, has_child, is collective]

    def close(item):
        end, name, start, has_child, coll_op = item
        if not has_child and not coll_op:
            if leaves and start <= leaves[-1][1]:
                leaves[-1][1] = max(leaves[-1][1], end)
            else:
                leaves.append([start, end])

    for full, s, e in events:
        name = short(full)
        coll_op = is_collective(full)
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        dur = e - s
        if stack:
            parent = stack[-1]
            parent[3] = True
            self_ns[parent[1]] = self_ns.get(parent[1], 0) - dur
        else:
            top.append([name, s, e])
        self_ns[name] = self_ns.get(name, 0) + dur
        if coll_op:
            coll.append([name, s, e])
        stack.append([e, name, s, False, coll_op])
    while stack:
        close(stack.pop())
    return {"top": top, "self": self_ns, "collectives": coll,
            "leaves": leaves}


def near(intervals, coll) -> list:
    """The intervals that meet some collective interval."""
    if not coll:
        return []
    spans = union((s, e) for _, s, e in coll)
    starts = [s for s, _ in spans]
    out = []
    for s, e in intervals:
        # the last collective span that starts before this interval ends
        i = bisect.bisect_left(starts, e) - 1
        if i >= 0 and spans[i][1] > s:
            out.append([s, e])
    return out


def extract(trace_dir) -> dict:
    """The plain form of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            red, extra = None, []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    red = reduce_line(
                        (e.name, int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                        for e in line.events)
                elif line.name == ASYNC_LINE:
                    for e in line.events:
                        if is_collective(e.name):
                            s = int(e.start_ns)
                            extra.append([short(e.name), s,
                                          s + int(e.duration_ns)])
            if red is not None:
                red["collectives"] = sorted(red["collectives"] + extra,
                                            key=lambda c: c[1])
                red["leaves"] = near(red["leaves"], red["collectives"])
                devices[m.group(1)] = red
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": sorted(host, key=lambda h: h[1])}


def save(plain: dict, path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(plain, f)


def load(path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def union(intervals) -> list:
    """Merged, sorted, disjoint intervals of ``(start, end)`` pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def subtract(a, b) -> list:
    """Parts of the disjoint sorted intervals ``a`` that no interval of
    the disjoint sorted ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclass
class Timeline:
    plain: dict

    def __post_init__(self):
        host = self.plain["host"]
        if not host:
            raise ValueError("trace holds no bench.* host annotation")
        self.t0 = min(h[1] for h in host)
        self.t1 = max(h[2] for h in host)
        if not self.plain["devices"]:
            raise ValueError("trace holds no TPU operation")

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def chips(self) -> list:
        return sorted(self.plain["devices"], key=int)

    def _dev(self, chip: str) -> dict:
        return self.plain["devices"][chip]

    def busy(self, chip: str) -> list:
        """Merged intervals in which the chip ran some operation."""
        return union(clip([(s, e) for _, s, e in self._dev(chip)["top"]],
                          self.t0, self.t1))

    def busy_ns(self, chip: str) -> int:
        return length(self.busy(chip))

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips."""
        return sum(self.busy_ns(c) for c in self.chips) \
            / len(self.chips) * 1e-9

    def idle_share(self) -> float:
        """1 - busy / window on the busiest chip."""
        busiest = max(self.busy_ns(c) for c in self.chips)
        return 1.0 - busiest / (self.t1 - self.t0)

    def collective_ns(self, chip: str) -> tuple:
        """(time in collective ops, the part of it in which no other,
        innermost operation runs on that chip)."""
        d = self._dev(chip)
        coll = union(clip([(s, e) for _, s, e in d["collectives"]],
                          self.t0, self.t1))
        other = union(clip([tuple(x) for x in d["leaves"]],
                           self.t0, self.t1))
        return length(coll), length(subtract(coll, other))

    def idle_gaps(self, chip: str) -> list:
        """Idle intervals of a chip inside the window."""
        return subtract([(self.t0, self.t1)], self.busy(chip))

    def host_phase(self, s: int, e: int) -> str:
        """The benchmark's host annotation that covers most of
        ``[s, e)``, the shorter one on ties."""
        best, best_key = "none", (0, 0)
        for name, hs, he in self.plain["host"]:
            if he <= s or hs >= e:
                continue
            key = (min(he, e) - max(hs, s), hs - he)
            if key > best_key:
                best, best_key = name, key
        return best

    def breakdown(self, top: int = 10) -> dict:
        """The operations with the most self time (averaged over the
        chips, over the whole trace) and the longest idle gaps of the
        busiest chip, named by the host phase that covered them."""
        per_op: dict = {}
        for c in self.chips:
            for name, ns in self._dev(c)["self"].items():
                per_op[name] = per_op.get(name, 0) + ns
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        busiest = max(self.chips, key=self.busy_ns)
        gaps = sorted(self.idle_gaps(busiest), key=lambda g: g[0] - g[1])
        return {"device_ops": [[n, v * 1e-9 / len(self.chips)]
                               for n, v in ops],
                "idle_gaps": [[self.host_phase(s, e), (e - s) * 1e-9]
                              for s, e in gaps[:top]]}
