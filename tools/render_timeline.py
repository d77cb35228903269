#!/usr/bin/env python
"""Render a time-series JSONL dump into one self-contained HTML/SVG
timeline: the gauge history of a long run (a simnet run sampling
`chain/health.py` ledgers per slot, or a fleet's merged store), one
panel per gauge family.

Input is the artifact `obs/timeseries.py::dump_wire_jsonl` writes: one
header line (interval, levels, point count), then one line per
(resolution, point) with plain gauge values and histogram-delta
percentile summaries. Output is a single HTML file with inline SVG —
no JavaScript, no external assets, nothing to fetch: the file opens in
a browser offline, years later.

Panels group dynamically-labelled gauge families onto shared axes:
``health[n0].participation_rate`` and ``health[n3].participation_rate``
render as two series on one ``health.participation_rate`` panel, so a
single sick node shows up as the diverging line, which is the whole
point of recording per-node families side by side.

Usage:
  python tools/render_timeline.py timeseries.jsonl \\
      -o timeline.html \\
      [--match REGEX] [--resolution SECONDS]

``--match`` filters gauge labels (default: the consensus health family
plus the telemetry plane's own gauges); ``--resolution`` picks which
retention ring to plot (default: the finest present).
"""
import argparse
import html
import json
import os
import re
import sys

# distinguishable on white, colorblind-aware (Okabe-Ito)
PALETTE = ("#0072B2", "#D55E00", "#009E73", "#CC79A7",
           "#E69F00", "#56B4E9", "#F0E442", "#000000")

DEFAULT_MATCH = r"^health[\[.]|^timeseries\.|^process\."

_FAMILY_RE = re.compile(r"^([a-z_]+)\[([^\]]+)\]\.(.+)$")

PANEL_W, PANEL_H = 920, 170
MARGIN_L, MARGIN_R, MARGIN_T, MARGIN_B = 70, 160, 24, 22
PLOT_W = PANEL_W - MARGIN_L - MARGIN_R
PLOT_H = PANEL_H - MARGIN_T - MARGIN_B


def load_rows(path):
    """(header, rows) from one dump_wire_jsonl artifact."""
    header, rows = None, []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            doc = json.loads(line)
            if header is None and "timeseries" in doc:
                header = doc
                continue
            if "resolution_s" in doc:
                rows.append(doc)
    return header or {}, rows


def split_label(label):
    """``health[n0].participation_rate`` -> ("health.participation_rate",
    "n0"); an unbracketed label is its own panel with one series."""
    m = _FAMILY_RE.match(label)
    if m:
        return f"{m.group(1)}.{m.group(3)}", m.group(2)
    return label, ""


def collect_panels(rows, match_re):
    """{panel: {series: [(t, value), ...]}} over the selected rows."""
    panels = {}
    for row in rows:
        t = float(row.get("t", 0.0))
        for label, value in row.get("gauges", {}).items():
            if not match_re.search(label):
                continue
            panel, series = split_label(label)
            panels.setdefault(panel, {}).setdefault(series, []).append(
                (t, float(value)))
    for series_map in panels.values():
        for pts in series_map.values():
            pts.sort()
    return panels


def _fmt(v):
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return f"{v:.4g}"


def render_panel(title, series_map):
    """One inline-SVG panel: every series as a polyline on shared axes."""
    all_pts = [p for pts in series_map.values() for p in pts]
    t_min = min(p[0] for p in all_pts)
    t_max = max(p[0] for p in all_pts)
    v_min = min(p[1] for p in all_pts)
    v_max = max(p[1] for p in all_pts)
    if t_max <= t_min:
        t_max = t_min + 1.0
    if v_max <= v_min:
        v_max = v_min + 1.0
    pad = (v_max - v_min) * 0.05
    v_min, v_max = v_min - pad, v_max + pad

    def sx(t):
        return MARGIN_L + (t - t_min) / (t_max - t_min) * PLOT_W

    def sy(v):
        return MARGIN_T + (1.0 - (v - v_min) / (v_max - v_min)) * PLOT_H

    out = [
        f'<svg viewBox="0 0 {PANEL_W} {PANEL_H}" width="{PANEL_W}" '
        f'height="{PANEL_H}" xmlns="http://www.w3.org/2000/svg" '
        f'role="img" aria-label="{html.escape(title, quote=True)}">',
        f'<text x="{MARGIN_L}" y="16" font-size="13" font-weight="bold" '
        f'font-family="monospace">{html.escape(title)}</text>',
        # plot frame + min/max gridlines
        f'<rect x="{MARGIN_L}" y="{MARGIN_T}" width="{PLOT_W}" '
        f'height="{PLOT_H}" fill="none" stroke="#ccc"/>',
    ]
    for frac in (0.25, 0.5, 0.75):
        y = MARGIN_T + PLOT_H * frac
        out.append(f'<line x1="{MARGIN_L}" y1="{y:.1f}" '
                   f'x2="{MARGIN_L + PLOT_W}" y2="{y:.1f}" '
                   f'stroke="#eee"/>')
    for v, anchor_y in ((v_max, MARGIN_T + 10),
                        (v_min, MARGIN_T + PLOT_H - 2)):
        out.append(f'<text x="{MARGIN_L - 6}" y="{anchor_y}" '
                   f'font-size="10" font-family="monospace" '
                   f'text-anchor="end">{_fmt(v)}</text>')
    for t, anchor in ((t_min, "start"), (t_max, "end")):
        out.append(f'<text x="{sx(t):.1f}" '
                   f'y="{MARGIN_T + PLOT_H + 14}" font-size="10" '
                   f'font-family="monospace" text-anchor="{anchor}">'
                   f't={_fmt(t)}s</text>')
    for i, (series, pts) in enumerate(sorted(series_map.items())):
        color = PALETTE[i % len(PALETTE)]
        coords = " ".join(f"{sx(t):.1f},{sy(v):.1f}" for t, v in pts)
        out.append(f'<polyline points="{coords}" fill="none" '
                   f'stroke="{color}" stroke-width="1.5"/>')
        last = pts[-1][1]
        ly = MARGIN_T + 12 + i * 14
        name = html.escape(series or title)
        out.append(f'<line x1="{MARGIN_L + PLOT_W + 8}" y1="{ly - 4}" '
                   f'x2="{MARGIN_L + PLOT_W + 24}" y2="{ly - 4}" '
                   f'stroke="{color}" stroke-width="2"/>')
        out.append(f'<text x="{MARGIN_L + PLOT_W + 28}" y="{ly}" '
                   f'font-size="10" font-family="monospace">'
                   f'{name} = {_fmt(last)}</text>')
    out.append("</svg>")
    return "\n".join(out)


def render_html(header, rows, match, resolution=None):
    match_re = re.compile(match)
    resolutions = sorted({float(r["resolution_s"]) for r in rows})
    if not resolutions:
        raise SystemExit("render_timeline: no points in the artifact")
    res = float(resolution) if resolution is not None else resolutions[0]
    selected = [r for r in rows if float(r["resolution_s"]) == res]
    if not selected:
        raise SystemExit(
            f"render_timeline: no points at resolution {res}s "
            f"(present: {resolutions})")
    panels = collect_panels(selected, match_re)
    parts = [
        "<!DOCTYPE html>",
        '<html><head><meta charset="utf-8">',
        "<title>telemetry timeline</title>",
        "<style>body{font-family:monospace;margin:24px;}"
        "svg{display:block;margin-bottom:10px;}</style>",
        "</head><body>",
        "<h2>telemetry timeline</h2>",
        f"<p>source interval {header.get('interval_s', '?')}s · "
        f"plotted resolution {_fmt(res)}s · "
        f"{len(selected)} points · retention rings "
        f"{[_fmt(r) for r in resolutions]} · "
        f"match <code>{html.escape(match)}</code></p>",
    ]
    if not panels:
        parts.append("<p><b>no gauge labels matched</b> — the run had "
                     "the matched families disabled?</p>")
    for title in sorted(panels):
        parts.append(render_panel(title, panels[title]))
    parts.append("</body></html>")
    return "\n".join(parts) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("jsonl", help="dump_wire_jsonl artifact to render")
    ap.add_argument("-o", "--out", default=None,
                    help="output HTML path (default: input with .html)")
    ap.add_argument("--match", default=DEFAULT_MATCH,
                    help="regex selecting gauge labels "
                         f"(default: {DEFAULT_MATCH!r})")
    ap.add_argument("--resolution", type=float, default=None,
                    help="retention ring to plot in seconds "
                         "(default: finest present)")
    args = ap.parse_args(argv)
    out = args.out or os.path.splitext(args.jsonl)[0] + ".html"
    header, rows = load_rows(args.jsonl)
    body = render_html(header, rows, args.match, args.resolution)
    with open(out, "w") as fh:
        fh.write(body)
    print(f"render_timeline: wrote {out} "
          f"({len(body)} bytes, {len(rows)} points read)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
