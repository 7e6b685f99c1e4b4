"""Reduction of a ``jax.profiler`` trace to the per-layer numbers.

From the ``.xplane.pb`` file of a traced window:

* the window: the host annotation ``bench.window`` the harness opens at
  the window's first ``make_batch`` and closes at the one after its last
  step;
* device busy time: the union of the intervals of the operations on each
  device plane's op line, clipped to the window, averaged over devices;
* per-op totals, under the HLO instruction names the trace gives the
  operations (a ``while`` or other control-flow op spans its body's ops,
  which are counted instead);
* custom calls joined to the compiled step's HLO: each Mosaic kernel's
  instruction, the kernel named in its body, its operand and result
  shapes;
* idle gaps: the stretches of the window in which no operation ran on a
  device, each tagged with the host span (``bench.<name>``) that overlaps
  it most;
* collective time: the union of the intervals of the collective
  operations on each device plane (``all-gather``, ``reduce-scatter``,
  ``all-reduce``, ``collective-permute``, ``all-to-all`` and their
  ``-start``/``-done`` halves, by HLO instruction name), and the part of
  it that no other operation on the same plane covers (exposed), both
  averaged over devices.  A one-chip trace has none and reads 0.
"""
from __future__ import annotations

import base64
import dataclasses
import re
from pathlib import Path

HOST_PREFIX = "bench."
WINDOW = "bench.window"
OP_LINES = ("XLA Ops",)            # a device plane's per-operation line


def find_xplane(root) -> Path:
    found = sorted(Path(root).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return found[-1]


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(i) for i in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(merged, lo, hi):
    """Complement of merged intervals within [lo, hi]."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def covered(a, b) -> float:
    """Length of the intersection of two merged, sorted interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce",
               "collective-permute", "all-to-all")


def is_collective(name: str) -> bool:
    """An HLO instruction name (``all-reduce-start.3``) of a collective,
    or of one of its asynchronous halves."""
    return re.sub(r"(\.\d+)+$", "", name).startswith(COLLECTIVES)


def collective_times(events) -> tuple[float, float]:
    """(collective, exposed) time of one device plane's ``(op name, start,
    end)`` events: the union of the collectives' intervals, and the part
    of it that no other operation's interval covers."""
    coll = union([(s, e) for n, s, e in events if is_collective(n)])
    other = union([(s, e) for n, s, e in events if not is_collective(n)])
    total = sum(e - s for s, e in coll)
    return total, total - covered(coll, other)


def tag(gap, spans):
    """Name of the host span overlapping ``gap`` most ("other" if none)."""
    best, name = 0.0, "other"
    for n, s, e in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best:
            best, name = ov, n
    return name


# ---------------------------------------------------------------------------
# compiled HLO
# ---------------------------------------------------------------------------

_SHAPE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_KERNEL = re.compile(rb"(_?[A-Za-z0-9_]*kernel)")


def _shapes(text: str):
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(text)]


def custom_calls(hlo_text: str) -> dict:
    """{instruction: {"kernel", "operands", "results"}} for every Mosaic
    custom call (``tpu_custom_call``) of the compiled module."""
    out = {}
    for line in (hlo_text or "").splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s+custom-call\(",
                     line)
        if not m:
            continue
        name, result = m.group(1), m.group(2)
        ops = re.search(
            r"operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}", line)
        body = re.search(r'"body":"([^"]*)"', line)
        kernel = None
        if body:
            km = _KERNEL.search(base64.b64decode(body.group(1) + "=="))
            kernel = km.group(1).decode() if km else None
        out[name] = {"kernel": kernel,
                     "operands": _shapes(ops.group(1)) if ops else [],
                     "results": _shapes(result)}
    return out


# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                  # averaged over device planes
    ops: dict                      # op name -> seconds, summed over devices
    calls: list                    # (op name, seconds) of every device op
    gap_list: list                 # (host span, seconds)
    n_devices: int
    kernels: dict                  # custom_calls() of the compiled step
    collective_s: float = 0.0      # averaged over device planes
    exposed_collective_s: float = 0.0

    def top_ops(self, n: int):
        return [[k, v] for k, v in
                sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int):
        return [[k, v] for k, v in
                sorted(self.gap_list, key=lambda kv: -kv[1])[:n]]

    def custom_seconds(self, kernel: str | None = None) -> float:
        """Device seconds in Mosaic custom calls (of one kernel if named)."""
        return sum(s for name, s in self.calls if name in self.kernels and
                   (kernel is None or self.kernels[name]["kernel"] == kernel))


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device op event; the trace names an
    op by its whole instruction text (``%name = shape op(...)``)."""
    m = re.match(r"%?([^\s=%]+)\s*=", event_name)
    return m.group(1) if m else event_name


CONTAINERS = (" while(", " conditional(", " call(")


def _is_container(event_name: str) -> bool:
    """A control-flow op whose event spans the ops of its body."""
    return any(c in event_name for c in CONTAINERS)


def _device_planes(pd):
    return [p for p in pd.planes
            if re.fullmatch(r"/device:TPU:\d+", p.name)]


def _op_line(plane):
    lines = {l.name: l for l in plane.lines}
    for n in OP_LINES:
        if n in lines:
            return lines[n]
    raise ValueError(f"{plane.name}: no op line among {sorted(lines)}")


def reduce(path, hlo_text: str | None = None) -> Reduction:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    spans, window = [], None
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for e in line.events:
                if not e.name.startswith(HOST_PREFIX):
                    continue
                s, t = e.start_ns, e.start_ns + e.duration_ns
                if e.name == WINDOW:
                    window = window or (s, t)
                else:
                    spans.append((e.name[len(HOST_PREFIX):], s, t))
    if window is None:
        raise ValueError("the trace has no bench.window annotation")
    lo, hi = window
    devs = _device_planes(pd)
    if not devs:
        raise ValueError("the trace has no TPU device plane")
    busy, ops, calls, gap_list = 0.0, {}, [], []
    coll, exposed = 0.0, 0.0
    for p in devs:
        iv, named = [], []
        for e in _op_line(p).events:
            s, t = e.start_ns, e.start_ns + e.duration_ns
            c = clip([(s, t)], lo, hi)
            if not c:
                continue
            iv.append(c[0])
            if _is_container(e.name):
                continue                   # its body's ops are counted
            d = (c[0][1] - c[0][0]) * 1e-9
            name = op_name(e.name)
            ops[name] = ops.get(name, 0.0) + d
            calls.append((name, d))
            named.append((name,) + c[0])
        merged = union(iv)
        busy += sum(e - s for s, e in merged) * 1e-9
        gap_list += [(tag(g, spans), (g[1] - g[0]) * 1e-9)
                     for g in gaps(merged, lo, hi)]
        c_ns, x_ns = collective_times(named)
        coll += c_ns * 1e-9
        exposed += x_ns * 1e-9
    n = len(devs)
    return Reduction(window_s=(hi - lo) * 1e-9, busy_s=busy / n,
                     ops=ops, calls=calls, gap_list=gap_list,
                     n_devices=n, kernels=custom_calls(hlo_text),
                     collective_s=coll / n, exposed_collective_s=exposed / n)
