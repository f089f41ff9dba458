"""Prometheus text exposition of the ``Metrics`` registries.

The service's JSON metrics endpoints are fine for one consumer polling
one run; a fleet wants ONE scrape target. This module renders any set
of registries (the scheduler's own, plus every live per-job registry
under ``job``/``host`` labels) in the Prometheus text exposition
format (version 0.0.4):

    # HELP stateright_chunks completed chunk dispatches ...
    # TYPE stateright_chunks counter
    stateright_chunks{host="0",job="j0001-twopc"} 42

Typing is derived from the canonical registries in ``obs/metrics.py``:
:data:`~stateright_tpu.obs.metrics.GAUGES` and
:data:`~stateright_tpu.obs.metrics.MAXIMA` render as ``gauge``,
everything else (counters and the cumulative phase timers) as
``counter``. HELP text comes from
:data:`~stateright_tpu.obs.metrics.GLOSSARY`; keys outside the
glossary still render (``untyped`` would be dishonest — unknown keys
follow the same counter-unless-gauge rule) so a consumer never loses a
metric to documentation lag.

Non-numeric registry values (the ``engine`` winner tag is a string)
are skipped: Prometheus samples are floats, and mangling strings into
label-encoded pseudo-metrics would double every consumer's cardinality
for one debugging field the JSON endpoints already serve.

:func:`validate_exposition` is the strict line-format checker the
tests round-trip ``GET /metrics`` through; it doubles as a parser
(returns the sample map) so asserting on served values needs no second
implementation.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, Mapping, Tuple

from .metrics import GAUGES, GLOSSARY, MAXIMA

#: metric-name prefix: one namespace for every series this repo exports
PREFIX = "stateright"

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_NAME_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")
#: one sample line: name{labels} value  (labels optional; no timestamp
#: — we serve instantaneous scrapes)
_SAMPLE_RE = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?:\{(?P<labels>[^{}]*)\})?'
    r' (?P<value>[-+]?(?:[0-9]*\.?[0-9]+(?:[eE][-+]?[0-9]+)?'
    r'|Inf|NaN))$')
_LABEL_RE = re.compile(
    r'^(?P<key>[a-zA-Z_][a-zA-Z0-9_]*)="(?P<val>(?:[^"\\]|\\.)*)"$')


def metric_type(key: str) -> str:
    """``gauge`` for point-in-time values (GAUGES and the observed
    MAXIMA — a maximum can fall back to a lower value on the next run,
    so ``counter`` monotonicity would lie), ``counter`` for everything
    else (counts and cumulative phase-timer seconds)."""
    if key in GAUGES or key in MAXIMA:
        return "gauge"
    return "counter"


def _escape_help(text: str) -> str:
    return text.replace("\\", "\\\\").replace("\n", "\\n")


def _escape_label(value: str) -> str:
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def render(rows: Iterable[Tuple[Mapping, Mapping]],
           prefix: str = PREFIX) -> str:
    """Render ``(labels, registry_snapshot)`` rows as one exposition.

    All series of one metric name land under a single HELP/TYPE block
    (the format forbids split blocks); rows are typically the
    scheduler's registry (empty labels) plus one row per live job.
    Duplicate (name, labels) series raise — two rows claiming the same
    identity is a caller bug a scrape must not paper over."""
    series: Dict[str, list] = {}
    order: list = []
    seen: set = set()
    for labels, snap in rows:
        lab = {str(k): str(v) for k, v in dict(labels).items()}
        for key in sorted(snap):
            value = snap[key]
            if isinstance(value, bool):
                value = int(value)
            if not isinstance(value, (int, float)):
                continue  # string gauges (engine=...) are JSON-only
            # span timers are dotted (span_s.mirror.pull): a dot is
            # no name character in the exposition format
            name = f"{prefix}_{key.replace('.', '_')}"
            if not _NAME_RE.match(name):
                continue  # defensively skip unrenderable keys
            ident = (name, tuple(sorted(lab.items())))
            if ident in seen:
                raise ValueError(
                    f"duplicate series {name} {lab!r}")
            seen.add(ident)
            if name not in series:
                series[name] = []
                order.append((name, key))
            series[name].append((lab, float(value)))
    lines = []
    for name, key in order:
        help_text = GLOSSARY.get(key)
        if help_text:
            lines.append(f"# HELP {name} {_escape_help(help_text)}")
        lines.append(f"# TYPE {name} {metric_type(key)}")
        for lab, value in series[name]:
            if lab:
                body = ",".join(
                    f'{k}="{_escape_label(v)}"'
                    for k, v in sorted(lab.items()))
                lines.append(f"{name}{{{body}}} {_format(value)}")
            else:
                lines.append(f"{name} {_format(value)}")
    return "\n".join(lines) + ("\n" if lines else "")


def _format(value: float) -> str:
    if float(value).is_integer() and abs(value) < 1e15:
        return str(int(value))
    return repr(float(value))


def validate_exposition(text: str) -> Dict[Tuple[str, tuple], float]:
    """STRICT line-format validation of one exposition body; returns
    ``{(name, ((label, value), ...)): sample}`` for round-trip
    assertions. Raises ``ValueError`` on the first violation:
    malformed comment/sample lines, a sample before its TYPE, a TYPE
    outside the known set, interleaved metric blocks, or duplicate
    series."""
    samples: Dict[Tuple[str, tuple], float] = {}
    typed: Dict[str, str] = {}
    closed: set = set()
    current: str = None
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("#"):
            parts = line.split(" ", 3)
            if len(parts) < 4 or parts[0] != "#" or \
                    parts[1] not in ("HELP", "TYPE"):
                raise ValueError(f"line {lineno}: bad comment {line!r}")
            _, kind, name, rest = parts
            if not _NAME_RE.match(name):
                raise ValueError(
                    f"line {lineno}: bad metric name {name!r}")
            if kind == "TYPE":
                if rest not in ("counter", "gauge", "histogram",
                                "summary", "untyped"):
                    raise ValueError(
                        f"line {lineno}: bad type {rest!r}")
                if name in typed:
                    raise ValueError(
                        f"line {lineno}: duplicate TYPE for {name}")
                typed[name] = rest
            if current is not None and current != name:
                closed.add(current)
            if name in closed:
                raise ValueError(
                    f"line {lineno}: metric block {name} reopened")
            current = name
            continue
        m = _SAMPLE_RE.match(line)
        if m is None:
            raise ValueError(f"line {lineno}: bad sample {line!r}")
        name = m.group("name")
        if name not in typed:
            raise ValueError(
                f"line {lineno}: sample for {name} before its TYPE")
        if current != name:
            raise ValueError(
                f"line {lineno}: sample for {name} outside its block")
        labels = []
        body = m.group("labels")
        if body:
            for part in _split_labels(body, lineno):
                lm = _LABEL_RE.match(part)
                if lm is None:
                    raise ValueError(
                        f"line {lineno}: bad label {part!r}")
                labels.append((lm.group("key"), lm.group("val")))
        ident = (name, tuple(labels))
        if ident in samples:
            raise ValueError(f"line {lineno}: duplicate series {ident}")
        samples[ident] = float(m.group("value"))
    return samples


def _split_labels(body: str, lineno: int) -> list:
    """Split ``k="v",k2="v2"`` on commas outside quoted values."""
    parts, buf, in_quote, escaped = [], [], False, False
    for ch in body:
        if escaped:
            buf.append(ch)
            escaped = False
        elif ch == "\\":
            buf.append(ch)
            escaped = True
        elif ch == '"':
            buf.append(ch)
            in_quote = not in_quote
        elif ch == "," and not in_quote:
            parts.append("".join(buf))
            buf = []
        else:
            buf.append(ch)
    if in_quote:
        raise ValueError(f"line {lineno}: unterminated label quote")
    if buf:
        parts.append("".join(buf))
    return parts
