"""Benchmark driver: one function per paper table/figure + software
benches.  Prints ``name,us_per_call,derived`` CSV.

Flags:
  --paper-only : skip software benches.
  --smoke      : CI gate subset — policy dots, the packed/fused
                 operand-bandwidth pipeline, the DPA-attention /
                 KV-cache suite, and the paged-cache serving engine;
                 no paper figures, no e2e train steps.
  --json PATH  : also dump rows as JSON (name/us_per_call/derived plus
                 any parsed ``key=<float>x`` derived metrics) — the
                 artifact `benchmarks/check_regression.py` gates on.
"""
from __future__ import annotations

import json
import os
import re
import sys

# allow `python benchmarks/run.py` from anywhere: the repo root (for the
# `benchmarks` package) and src/ (for `repro`) both go on sys.path
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "src"))

_DERIVED_RE = re.compile(r"([A-Za-z0-9_]+)=([-+0-9.eE]+)x?")


def parse_derived(derived: str) -> dict:
    """``key=VALx`` tokens in a derived string -> {key: float}."""
    return {k: float(v) for k, v in _DERIVED_RE.findall(derived)}


def main() -> None:
    from benchmarks import attention_bench, engine_bench, software_bench
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    json_path = None
    if "--json" in sys.argv:
        i = sys.argv.index("--json") + 1
        if i >= len(sys.argv) or sys.argv[i].startswith("--"):
            raise SystemExit("--json needs an output path, e.g. "
                             "--json bench.json")
        json_path = sys.argv[i]
    if "--smoke" in sys.argv:
        suites = (list(software_bench.SMOKE) + list(attention_bench.SMOKE)
                  + list(engine_bench.SMOKE))
    else:
        # imported only here: the DPA golden model behind the paper tables
        # turns on jax_enable_x64 for the whole process, which the
        # serving benches must not run under
        from benchmarks import paper_tables
        suites = list(paper_tables.ALL)
        if "--paper-only" not in sys.argv:
            suites += (list(software_bench.ALL) + list(attention_bench.ALL)
                       + list(engine_bench.ALL))
    print("name,us_per_call,derived")
    rows = []
    failures = []
    for fn in suites:
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.1f},{derived}")
                rows.append({"name": name, "us_per_call": us,
                             "derived": derived,
                             "metrics": parse_derived(derived)})
        except Exception as e:                      # pragma: no cover
            failures.append((fn.__name__, repr(e)))
            print(f"{fn.__name__},ERROR,{e!r}")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(rows, f, indent=1)
        print(f"# wrote {len(rows)} rows to {json_path}", file=sys.stderr)
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
