"""The residual table: the §2.6 model against the measured spans.

``residual_rows`` / ``format_residual_table`` turn a
:class:`~repro.obs.trace.Tracer`'s spans into the §2.6
model-vs-measured artifact: one row per stage attempt with measured
wall seconds, predicted seconds, the residual, and the counted
collective footprint. (The spans' timeline is the JAX profiler's own
trace: a recording tracer writes each span there as a host annotation.)
"""
from __future__ import annotations


# --------------------------------------------------------------------------
# model-vs-measured residuals
# --------------------------------------------------------------------------

def residual_rows(tracer) -> list[dict]:
    """One row per span carrying a §2.6 prediction (stage attempts and
    front-door pipeline attempts), in execution order."""
    rows = []
    for s in tracer.spans:
        if "predicted_s" not in s.args or s.t1 is None:
            continue
        measured = s.duration
        predicted = float(s.args["predicted_s"])
        rows.append({
            "stage": s.args.get("stage", s.name),
            "level": s.args.get("level", -1),
            "attempt": s.args.get("attempt", 1),
            "measured_s": measured,
            "predicted_s": predicted,
            "residual_s": measured - predicted,
            "ratio": (measured / predicted) if predicted > 0 else float("inf"),
            "collectives": s.args.get("collective_count", 0),
            "payload_bytes": s.args.get("payload_bytes", 0),
        })
    return rows


def format_residual_table(rows: list[dict], title: str | None = None) -> str:
    """Aligned text rendering of the per-stage residual table."""
    header = ("stage", "lvl", "try", "measured", "predicted", "residual",
              "ratio", "colls", "bytes")
    body = []
    for r in rows:
        body.append((
            str(r["stage"]), str(r["level"]), str(r["attempt"]),
            _fmt_s(r["measured_s"]), _fmt_s(r["predicted_s"]),
            _fmt_s(r["residual_s"]),
            ("inf" if r["ratio"] == float("inf") else f"{r['ratio']:.1f}x"),
            str(r["collectives"]), str(r["payload_bytes"])))
    widths = [max(len(header[i]), *(len(row[i]) for row in body))
              if body else len(header[i]) for i in range(len(header))]
    lines = [] if title is None else [title]
    lines.append("  ".join(h.ljust(w) for h, w in zip(header, widths)))
    lines.append("  ".join("-" * w for w in widths))
    for row in body:
        lines.append("  ".join(c.ljust(w) for c, w in zip(row, widths)))
    if not body:
        lines.append("(no predicted spans recorded)")
    return "\n".join(lines)


def _fmt_s(v: float) -> str:
    a = abs(v)
    if a >= 1.0:
        return f"{v:.3f}s"
    if a >= 1e-3:
        return f"{v * 1e3:.2f}ms"
    return f"{v * 1e6:.1f}us"


def residual_summary(rows: list[dict]) -> dict:
    """Headline numbers for trend records: totals and the worst
    per-stage over/under-prediction ratio."""
    if not rows:
        return {"stages": 0, "measured_s": 0.0, "predicted_s": 0.0}
    measured = sum(r["measured_s"] for r in rows)
    predicted = sum(r["predicted_s"] for r in rows)
    finite = [r["ratio"] for r in rows if r["ratio"] != float("inf")]
    return {
        "stages": len(rows),
        "measured_s": measured,
        "predicted_s": predicted,
        "total_ratio": (measured / predicted) if predicted > 0 else None,
        "max_ratio": max(finite) if finite else None,
        "min_ratio": min(finite) if finite else None,
    }


__all__ = ["residual_rows", "format_residual_table", "residual_summary"]
