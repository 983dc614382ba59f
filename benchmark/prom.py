"""Prometheus text exposition -> deltas over a window.

The program's registry (``janus_tpu.core.metrics.GLOBAL_METRICS``) is read
only through its text export, the same bytes ``GET /metrics`` serves, so the
arithmetic here holds for either registry implementation.
"""

from __future__ import annotations

import fnmatch
import re

_SAMPLE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse(text):
    """Exposition text -> {(sample name, ((label, value), ...)): float}."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line.strip())
        if not m:
            continue
        name, labels, value = m.groups()
        try:
            number = float(value)
        except ValueError:
            continue
        key = (name, tuple(sorted(_LABEL.findall(labels or ""))))
        out[key] = out.get(key, 0.0) + number
    return out


def total(samples, name, labels=None):
    """Sum of one sample name over every label set that carries ``labels``;
    a wanted value with ``*`` in it is a glob."""
    want = dict(labels or {})

    def matches(have):
        have = dict(have)
        for k, v in want.items():
            got = have.get(k)
            if got is None:
                return False
            if not (fnmatch.fnmatchcase(got, v) if "*" in v else got == v):
                return False
        return True

    return sum(v for (n, have), v in samples.items() if n == name and matches(have))


def delta(before, after, name, labels=None):
    return total(after, name, labels) - total(before, name, labels)


def mean(before, after, family, labels=None):
    """Mean observation of a histogram family between two snapshots:
    delta of ``_sum`` over delta of ``_count``; ``None`` with no
    observation in between."""
    count = delta(before, after, family + "_count", labels)
    if count <= 0:
        return None
    return delta(before, after, family + "_sum", labels) / count


def snapshot():
    from janus_tpu.core.metrics import GLOBAL_METRICS

    return parse(GLOBAL_METRICS.export().decode())
