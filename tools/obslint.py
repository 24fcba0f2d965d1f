"""Metric-catalog lint: code series and docs/observability.md must agree.

Every PR so far has added monitor series, and the catalog in
docs/observability.md keeps them findable — but nothing enforced the
pairing, and undocumented series are invisible to the dashboards and
alerts built off the doc. This tool closes the loop statically:

- **code -> docs**: every literal series name passed to
  ``monitor.inc`` / ``monitor.observe`` / ``monitor.set_gauge`` anywhere
  under ``paddle_tpu/`` must appear (backticked) in
  docs/observability.md. Dynamically-built names (``'%s_bytes' % site``)
  are invisible to the scan and must be covered by documenting each
  concrete name.
- **docs -> code**: every backticked token in the doc that *looks like*
  a series name (``*_total``/``*_seconds``/``*_bytes``/``*_errors``)
  must exist in code — a curated allowlist covers names the scan cannot
  see because code builds them dynamically.

Run as a CLI (exit 1 + a drift report) or via the tier-1 test in
tests/test_obslint.py, which is what keeps new series from landing
undocumented.

Usage:
    python tools/obslint.py            # lint the repo this file lives in
"""
import os
import re
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# monitor.inc('name'...) / monitor.observe('name'...) /
# monitor.set_gauge('name'...), first argument a string literal —
# possibly on the next line after the open paren. timed_span's SECOND
# argument is the histogram series it observes into, phase's the seconds
# counter it adds to; executor.py's _count() is a thin monitor.inc
# wrapper (the donation ledger).
_CALL_RE = re.compile(
    r"monitor\.(inc|observe|set_gauge)\(\s*'([A-Za-z0-9_.]+)'", re.S)
_SPAN_RE = re.compile(
    r"monitor\.(?:timed_span|phase)\(\s*(?:'[A-Za-z0-9_.:]+'(?:\s*\+\s*\w+)?"
    r"|\w+),\s*'([A-Za-z0-9_.]+)'", re.S)
_HELPER_RE = re.compile(r"\b_count\(\s*'([A-Za-z0-9_.]+)'", re.S)

# any quoted token with a series suffix, wherever it appears — the
# docs->code direction accepts these too, so table-driven emitters
# (goodput's per-signature export loop iterates ('goodput_flops_total',
# idx) pairs) don't need allowlisting
_LITERAL_RE = re.compile(r"'([A-Za-z0-9_.]+)'")

# backticked tokens in the doc; a trailing {label=...} annotation is
# part of the catalog style, not the series name
_DOC_TOKEN_RE = re.compile(r'`([A-Za-z0-9_.]+)(?:\{[^`]*\})?`')

# doc tokens with these suffixes are claimed series names and must
# resolve against the code scan (everything else backticked — knobs,
# file names, functions — is ignored)
_SERIES_SUFFIXES = ('_total', '_seconds', '_bytes', '_errors')

# doc-listed series the static scan cannot see: code builds the name
# dynamically (site-parameterized '%s_bytes' templates) or increments it
# through a helper. Each entry names its construction site.
DOC_ALLOWLIST = {
    'ps_pull_bytes',        # ps/transport.py: '%s_bytes' % site
    'ps_push_bytes',        # ps/transport.py: '%s_bytes' % site
    'ps_admin_bytes',       # ps/transport.py: '%s_bytes' % site
}


def collect_code_series(root=None):
    """({series_name: [relpath, ...]}, mentioned): emission sites found
    by the call-shape scan, plus the looser set of ALL series-suffixed
    string literals (the docs->code direction accepts a mention, so
    table-driven emitters don't need allowlisting)."""
    root = root or os.path.join(_REPO, 'paddle_tpu')
    out, mentioned = {}, set()
    for dirpath, _dirnames, filenames in os.walk(root):
        for fn in sorted(filenames):
            if not fn.endswith('.py'):
                continue
            path = os.path.join(dirpath, fn)
            with open(path) as f:
                src = f.read()
            rel = os.path.relpath(path, _REPO)
            for _kind, name in _CALL_RE.findall(src):
                out.setdefault(name, []).append(rel)
            for name in _SPAN_RE.findall(src):
                out.setdefault(name, []).append(rel)
            for name in _HELPER_RE.findall(src):
                out.setdefault(name, []).append(rel)
            mentioned.update(t for t in _LITERAL_RE.findall(src)
                             if t.endswith(_SERIES_SUFFIXES))
    return out, mentioned


def collect_doc_series(doc_path=None):
    """Set of backticked tokens in docs/observability.md."""
    doc_path = doc_path or os.path.join(_REPO, 'docs', 'observability.md')
    with open(doc_path) as f:
        text = f.read()
    return {m.group(1) for m in _DOC_TOKEN_RE.finditer(text)}


def lint(root=None, doc_path=None):
    """Returns (undocumented, unknown): code series missing from the doc,
    and doc-claimed series (by suffix) with no mention anywhere in code
    minus the allowlist. Both empty = catalog and code agree."""
    code, mentioned = collect_code_series(root)
    doc = collect_doc_series(doc_path)
    undocumented = {n: sites for n, sites in sorted(code.items())
                    if n not in doc}
    unknown = sorted(
        t for t in doc
        if t.endswith(_SERIES_SUFFIXES)
        and t not in code
        and t not in mentioned
        and t not in DOC_ALLOWLIST)
    return undocumented, unknown


def main(argv=None):
    undocumented, unknown = lint()
    ok = True
    if undocumented:
        ok = False
        sys.stdout.write(
            'UNDOCUMENTED series (in code, missing from '
            'docs/observability.md):\n')
        for name, sites in undocumented.items():
            sys.stdout.write('  %-44s %s\n'
                             % (name, ', '.join(sorted(set(sites)))))
    if unknown:
        ok = False
        sys.stdout.write(
            'UNKNOWN series (documented, not found in code; add to '
            'DOC_ALLOWLIST only for dynamically-built names):\n')
        for name in unknown:
            sys.stdout.write('  %s\n' % name)
    if ok:
        sys.stdout.write('observability catalog and code agree (%d '
                         'series)\n' % len(collect_code_series()[0]))
        return 0
    return 1


if __name__ == '__main__':
    raise SystemExit(main())
