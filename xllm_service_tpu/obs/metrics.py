"""Lock-cheap metrics registry + Prometheus text-exposition rendering.

One registry per component (scheduler, engine, HTTP plane) rather than one
process-global singleton: tests and benches run a whole cluster — master +
N instances — inside a single process, and per-component registries keep
their series from bleeding into each other. A component exposes itself by
rendering its registry; the master aggregates by parsing scraped instance
expositions and re-emitting every sample under an `instance` label with ONE
`# TYPE` line per family (the text parser rejects duplicate TYPE lines and
ungrouped series, which would fail the whole scrape).

Conventions (enforced at registration, linted by
scripts/check_metric_names.py):
  * every name matches ^xllm_[a-z0-9_]+$;
  * counters end in `_total`;
  * histograms render `_bucket` (cumulative, `le` labels, `+Inf`),
    `_sum`, `_count`.

Hot-path cost: a labeled child is resolved once and cached by the caller;
inc/observe take one short per-child lock (allocation-free).
"""

from __future__ import annotations

import bisect
import re
import threading
from collections import OrderedDict
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

_NAME_RE = re.compile(r"^xllm_[a-z0-9_]+$")
_LABEL_RE = re.compile(r"^[a-z_][a-z0-9_]*$")

# Fixed log-spaced latency buckets (ms), shared by every latency histogram
# in the system so fleet-wide quantiles aggregate exactly: a 1-2-5 ladder
# from 1 ms to 60 s covers TTFT, TPOT, queue delay, and E2E alike.
LATENCY_BUCKETS_MS: Tuple[float, ...] = (
    1, 2, 5, 10, 20, 50, 100, 200, 500,
    1000, 2000, 5000, 10000, 30000, 60000,
)

# Power-of-two occupancy buckets (batch sizes, queue depths).
BATCH_BUCKETS: Tuple[float, ...] = (1, 2, 4, 8, 16, 32, 64, 128, 256)


def _fmt_num(v: float) -> str:
    """Prometheus sample value: integral floats render without the dot."""
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def _escape_label_value(v: str) -> str:
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _label_str(labels: Dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(
        f'{k}="{_escape_label_value(v)}"' for k, v in sorted(labels.items())
    )
    return "{" + inner + "}"


class _Child:
    """One (metric, label-set) time series."""

    __slots__ = ("_lock", "_value", "_fn")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._value = 0.0
        self._fn: Optional[Callable[[], float]] = None

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    def dec(self, n: float = 1.0) -> None:
        with self._lock:
            self._value -= n

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    def set_function(self, fn: Callable[[], float]) -> None:
        """Pull the value from `fn` at render time instead of storing it —
        exposes an existing counter attribute or queue length without
        instrumenting its hot path. The source must stay monotonic when
        the parent metric is a Counter."""
        self._fn = fn

    def get(self) -> float:
        fn = self._fn
        if fn is not None:
            try:
                return float(fn())
            except Exception:
                return 0.0
        with self._lock:
            return self._value


class _Metric:
    kind = "untyped"

    def __init__(
        self,
        name: str,
        help: str = "",
        labelnames: Sequence[str] = (),
    ) -> None:
        if not _NAME_RE.match(name):
            raise ValueError(
                f"metric name {name!r} must match {_NAME_RE.pattern}"
            )
        for ln in labelnames:
            if not _LABEL_RE.match(ln):
                raise ValueError(f"bad label name {ln!r} on {name}")
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self._children: "OrderedDict[Tuple[str, ...], _Child]" = OrderedDict()
        self._children_mu = threading.Lock()
        if not self.labelnames:
            self._default = self._make_child()
            self._children[()] = self._default
        else:
            self._default = None

    def _make_child(self) -> _Child:
        return _Child()

    def labels(self, **kv: str) -> _Child:
        if set(kv) != set(self.labelnames):
            raise ValueError(
                f"{self.name}: labels {sorted(kv)} != declared "
                f"{sorted(self.labelnames)}"
            )
        key = tuple(str(kv[ln]) for ln in self.labelnames)
        with self._children_mu:
            child = self._children.get(key)
            if child is None:
                child = self._children[key] = self._make_child()
        return child

    def _iter_children(self) -> List[Tuple[Dict[str, str], _Child]]:
        with self._children_mu:
            items = list(self._children.items())
        return [
            (dict(zip(self.labelnames, key)), child) for key, child in items
        ]

    # -- unlabeled conveniences ---------------------------------------- #
    def _only(self) -> _Child:
        if self._default is None:
            raise ValueError(f"{self.name} is labeled; use .labels(...)")
        return self._default

    def inc(self, n: float = 1.0) -> None:
        self._only().inc(n)

    def set(self, v: float) -> None:
        self._only().set(v)

    def set_function(self, fn: Callable[[], float]) -> None:
        self._only().set_function(fn)

    def get(self) -> float:
        return self._only().get()

    # -- rendering ------------------------------------------------------ #
    def collect(self) -> List[Tuple[str, str]]:
        """[(labels_str, value_str)] sample lines (name prepended later)."""
        return [
            (_label_str(labels), _fmt_num(child.get()))
            for labels, child in self._iter_children()
        ]


class Counter(_Metric):
    kind = "counter"

    def __init__(self, name, help="", labelnames=()):
        if not name.endswith("_total"):
            raise ValueError(f"counter {name!r} must end in _total")
        super().__init__(name, help, labelnames)

    def dec(self, n: float = 1.0) -> None:  # pragma: no cover — guard
        raise TypeError("counters only go up")

    def set(self, v: float) -> None:  # pragma: no cover — guard
        raise TypeError("counters only go up; use inc() or set_function()")


class Gauge(_Metric):
    kind = "gauge"

    def dec(self, n: float = 1.0) -> None:
        self._only().dec(n)


class _HistChild:
    __slots__ = ("_lock", "_bounds", "counts", "sum", "count")

    def __init__(self, bounds: Tuple[float, ...]):
        self._lock = threading.Lock()
        self._bounds = bounds
        self.counts = [0] * (len(bounds) + 1)  # last = +Inf overflow
        self.sum = 0.0
        self.count = 0

    def observe(self, v: float) -> None:
        idx = bisect.bisect_left(self._bounds, v)
        with self._lock:
            self.counts[idx] += 1
            self.sum += v
            self.count += 1

    def observe_many(self, vs: Sequence[float]) -> None:
        """`observe` for each of `vs` under ONE acquisition (a step's
        values at once): the same buckets, sum and count."""
        idxs = [bisect.bisect_left(self._bounds, v) for v in vs]
        with self._lock:
            for idx, v in zip(idxs, vs):
                self.counts[idx] += 1
                self.sum += v
            self.count += len(idxs)

    def snapshot(self) -> Tuple[List[int], float, int]:
        with self._lock:
            return list(self.counts), self.sum, self.count

    def percentile(self, q: float) -> Optional[float]:
        """Bucket-interpolated quantile estimate (q in [0, 100]). None when
        empty; the +Inf bucket clamps to the largest finite bound."""
        counts, _, total = self.snapshot()
        if total == 0:
            return None
        target = max(1.0, (q / 100.0) * total)
        cum = 0
        for i, c in enumerate(counts):
            prev_cum = cum
            cum += c
            if cum >= target:
                if i >= len(self._bounds):
                    return float(self._bounds[-1])
                lo = self._bounds[i - 1] if i > 0 else 0.0
                hi = self._bounds[i]
                frac = (target - prev_cum) / max(c, 1)
                return float(lo + (hi - lo) * frac)
        return float(self._bounds[-1])


class Histogram(_Metric):
    kind = "histogram"

    def __init__(
        self,
        name,
        help="",
        labelnames=(),
        buckets: Sequence[float] = LATENCY_BUCKETS_MS,
    ):
        for suffix in ("_total", "_bucket", "_sum", "_count"):
            if name.endswith(suffix):
                raise ValueError(
                    f"histogram {name!r} must not end in {suffix} "
                    "(those suffixes are render-reserved)"
                )
        b = tuple(float(x) for x in buckets)
        if not b or list(b) != sorted(set(b)):
            raise ValueError("buckets must be sorted and distinct")
        self.buckets = b
        super().__init__(name, help, labelnames)

    def _make_child(self) -> _HistChild:
        return _HistChild(self.buckets)

    def observe(self, v: float) -> None:
        self._only().observe(v)

    def observe_many(self, vs: Sequence[float]) -> None:
        self._only().observe_many(vs)

    def percentile(self, q: float) -> Optional[float]:
        return self._only().percentile(q)

    def collect(self) -> List[Tuple[str, str]]:
        """Histogram expands to _bucket/_sum/_count sample lines; the
        returned labels_str here carries the FULL sample name because the
        suffixes differ per line (render() special-cases kind)."""
        out: List[Tuple[str, str]] = []
        for labels, child in self._iter_children():
            counts, total_sum, n = child.snapshot()
            cum = 0
            for bound, c in zip(self.buckets, counts):
                cum += c
                ls = _label_str({**labels, "le": _fmt_num(bound)})
                out.append((f"{self.name}_bucket{ls}", _fmt_num(cum)))
            ls = _label_str({**labels, "le": "+Inf"})
            out.append((f"{self.name}_bucket{ls}", _fmt_num(n)))
            out.append(
                (f"{self.name}_sum{_label_str(labels)}", _fmt_num(total_sum))
            )
            out.append(
                (f"{self.name}_count{_label_str(labels)}", _fmt_num(n))
            )
        return out


class MetricsRegistry:
    """Create-or-get metric factory + renderer for one component."""

    def __init__(self) -> None:
        self._metrics: "OrderedDict[str, _Metric]" = OrderedDict()
        self._mu = threading.Lock()

    def _register(self, cls, name, help, labelnames, **kw) -> _Metric:
        with self._mu:
            m = self._metrics.get(name)
            if m is not None:
                if type(m) is not cls:
                    raise ValueError(
                        f"{name} already registered as {m.kind}"
                    )
                return m
            m = cls(name, help, labelnames, **kw)
            self._metrics[name] = m
            return m

    def counter(self, name, help="", labelnames=()) -> Counter:
        return self._register(Counter, name, help, labelnames)

    def gauge(self, name, help="", labelnames=()) -> Gauge:
        return self._register(Gauge, name, help, labelnames)

    def histogram(
        self, name, help="", labelnames=(), buckets=LATENCY_BUCKETS_MS
    ) -> Histogram:
        return self._register(
            Histogram, name, help, labelnames, buckets=buckets
        )

    def get(self, name: str) -> Optional[_Metric]:
        with self._mu:
            return self._metrics.get(name)

    def names(self) -> List[Tuple[str, str]]:
        """[(name, kind)] of everything registered (lint surface)."""
        with self._mu:
            return [(m.name, m.kind) for m in self._metrics.values()]

    def families(self) -> "OrderedDict[str, Tuple[str, str, List[Tuple[str, str]]]]":
        """name -> (kind, help, [(sample_suffix_or_labels, value)]).

        For counter/gauge the first tuple element is the label string to
        append to the family name; for histograms it is the FULL sample
        name (suffix + labels) and the family name must not be prepended.
        render_families() handles both via the histogram kind.
        """
        with self._mu:
            metrics = list(self._metrics.values())
        fams: "OrderedDict[str, Tuple[str, str, List[Tuple[str, str]]]]" = (
            OrderedDict()
        )
        for m in metrics:
            fams[m.name] = (m.kind, m.help, m.collect())
        return fams

    def render(self) -> str:
        return render_families(self.families())


# --------------------------------------------------------------------- #
# exposition text: render / parse / merge (master-side aggregation)
# --------------------------------------------------------------------- #

def render_families(fams) -> str:
    """One text exposition from a families dict — exactly one HELP/TYPE
    pair per family, every sample grouped contiguously under it.

    HISTOGRAM families with NO samples are omitted entirely: a labelled
    histogram nobody has observed yet (e.g. the scrape-latency histogram
    on the very first exposure, whose observations land DURING the
    scrape the exposition is being built for) would otherwise render a
    TYPE-only header, which a strict scraper rejects as a histogram
    without `_bucket` samples. Empty counter/gauge families keep their
    TYPE-only header — that IS valid exposition, and tests and dashboards
    discover series names from it."""
    lines: List[str] = []
    for name, (kind, help_text, samples) in fams.items():
        if not samples and kind == "histogram":
            continue
        if help_text:
            lines.append(f"# HELP {name} {help_text}")
        lines.append(f"# TYPE {name} {kind}")
        for head, value in samples:
            if kind == "histogram":
                lines.append(f"{head} {value}")
            else:
                lines.append(f"{name}{head} {value}")
    return "\n".join(lines) + "\n" if lines else ""


_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)\s*$"
)
_LABEL_PAIR_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _family_of(sample_name: str, known: Dict[str, str]) -> str:
    """Map a histogram sample name back to its family."""
    for suffix in ("_bucket", "_sum", "_count"):
        base = sample_name[: -len(suffix)]
        if sample_name.endswith(suffix) and known.get(base) == "histogram":
            return base
    return sample_name


def parse_exposition(text: str):
    """Parse Prometheus text into an OrderedDict:
    name -> (kind, help, [(sample_name, labels_dict, value_str)]).

    Tolerant: unknown families default to `untyped`; values stay strings
    so re-rendering never drifts a float. Used by the master to re-label
    scraped instance expositions before merging."""
    fams: "OrderedDict[str, List]" = OrderedDict()
    kinds: Dict[str, str] = {}
    helps: Dict[str, str] = {}
    for raw in text.splitlines():
        line = raw.strip()
        if not line:
            continue
        if line.startswith("# TYPE "):
            parts = line.split(None, 3)
            if len(parts) >= 4:
                kinds[parts[2]] = parts[3]
                fams.setdefault(parts[2], [])
            continue
        if line.startswith("# HELP "):
            parts = line.split(None, 3)
            if len(parts) >= 3:
                helps[parts[2]] = parts[3] if len(parts) > 3 else ""
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            continue
        sample_name, labels_raw, value = m.groups()
        labels = dict(_LABEL_PAIR_RE.findall(labels_raw or ""))
        fam = _family_of(sample_name, kinds)
        fams.setdefault(fam, []).append((sample_name, labels, value))
    return OrderedDict(
        (
            name,
            (kinds.get(name, "untyped"), helps.get(name, ""), samples),
        )
        for name, samples in fams.items()
    )


def absorb_exposition(
    fams,
    text: str,
    extra_labels: Optional[Dict[str, str]] = None,
) -> List[str]:
    """Merge one exposition into a render_families()-shaped dict, adding
    `extra_labels` to every sample. Families that already exist keep their
    first-seen kind/help and the new samples append under the SAME single
    TYPE line — the whole point of aggregation (a second TYPE line would
    fail strict scrapers). Kind conflicts (a family whose incoming # TYPE
    disagrees with the first-seen one) deterministically SKIP the incoming
    samples — first-seen kind wins regardless of merge order within a
    family — and the skipped family names are returned so callers can
    count them instead of losing series silently."""
    # Parsed label values are kept in their ESCAPED wire form; only the
    # extra labels need escaping here — re-escaping parsed values would
    # drift a backslash/quote-bearing value on every aggregation hop.
    extra = {
        k: _escape_label_value(v) for k, v in (extra_labels or {}).items()
    }

    def label_str_raw(escaped: Dict[str, str]) -> str:
        if not escaped:
            return ""
        inner = ",".join(
            f'{k}="{v}"' for k, v in sorted(escaped.items())
        )
        return "{" + inner + "}"

    conflicts: List[str] = []
    for name, (kind, help_text, samples) in parse_exposition(text).items():
        rendered: List[Tuple[str, str]] = []
        for sample_name, labels, value in samples:
            merged = {**labels, **extra}
            if kind == "histogram":
                rendered.append(
                    (f"{sample_name}{label_str_raw(merged)}", value)
                )
            else:
                rendered.append((label_str_raw(merged), value))
        if name in fams:
            prev_kind, prev_help, prev_samples = fams[name]
            if prev_kind != kind:
                conflicts.append(name)
                continue
            fams[name] = (prev_kind, prev_help, prev_samples + rendered)
        else:
            fams[name] = (kind, help_text, rendered)
    return conflicts
