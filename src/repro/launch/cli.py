"""The umbrella CLI: ``python -m repro <surface> <verb> ...``.

One entrypoint over the six launch surfaces — each sub-CLI keeps its own
parser (registered here, never duplicated) and stays invocable as
``python -m repro.launch.X`` for old scripts (a thin alias that prints a
one-line deprecation pointer):

    python -m repro census  run --out DIR --workers 4   # DiscriminantSweep
    python -m repro explain run --census DIR --out E    # AnomalyExplainer
    python -m repro queue   work --out DIR              # pull-based drain
    python -m repro fsck    --out DIR [--dry-run]       # repair any store
    python -m repro oracle  warm --out C --census DIR   # ranking service
    python -m repro predict train --census DIR --out M  # learned cost model

Dispatch is manual (argv[0] lookup, remainder forwarded verbatim) rather
than argparse-subparser composition: every surface's ``main(argv, prog=)``
owns its full argparse tree, and the umbrella just rebrands ``prog`` so
``--help`` prints the command the user actually typed.
"""

from __future__ import annotations

import os
import sys
from typing import Callable, List, Optional, Tuple

#: JAX's persistent compilation cache when ``JAX_COMPILATION_CACHE_DIR``
#: names none: a fixed directory inside the checkout, because the path is
#: part of the cache's key and a directory that moves never hits
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache where
    ``JAX_COMPILATION_CACHE_DIR`` says, else in :data:`COMPILE_CACHE_DIR`.
    JAX reads the variable when it is imported, so call this first; it
    imports no jax itself. Returns the directory in use."""
    return os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", COMPILE_CACHE_DIR)


def _census_main(argv: List[str], prog: str) -> int:
    from repro.launch.sweep import main

    return main(argv, prog=prog)


def _explain_main(argv: List[str], prog: str) -> int:
    from repro.launch.explain import main

    return main(argv, prog=prog)


def _queue_main(argv: List[str], prog: str) -> int:
    from repro.launch.queue import main

    return main(argv, prog=prog)


def _fsck_main(argv: List[str], prog: str) -> int:
    from repro.launch.fsck import main

    return main(argv, prog=prog)


def _oracle_main(argv: List[str], prog: str) -> int:
    from repro.launch.oracle import main

    return main(argv, prog=prog)


def _predict_main(argv: List[str], prog: str) -> int:
    from repro.launch.predict import main

    return main(argv, prog=prog)


#: surface name -> (dispatcher, one-line help). Lazy imports keep
#: ``python -m repro --help`` free of every surface's dependency tree.
SURFACES: "dict[str, Tuple[Callable[[List[str], str], int], str]]" = {
    "census": (_census_main,
               "plan/run/merge/report the FLOPs-discriminant census"),
    "explain": (_explain_main,
                "explain the census's anomalies (root-cause campaigns)"),
    "queue": (_queue_main,
              "drain any campaign store with pull-based multi-host workers"),
    "fsck": (_fsck_main,
             "classify/repair/quarantine damage in any campaign store"),
    "oracle": (_oracle_main,
               "warm/query/serve the ranking-as-a-service cache"),
    "predict": (_predict_main,
                "train/apply the learned cost model (active censuses)"),
}


def _usage() -> str:
    lines = [
        "usage: python -m repro <surface> <verb> [options]",
        "",
        "surfaces:",
    ]
    for name, (_, help_line) in SURFACES.items():
        lines.append(f"  {name:<8} {help_line}")
    lines += [
        "",
        "run `python -m repro <surface> --help` for that surface's verbs.",
    ]
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    use_compile_cache()
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print(_usage())
        return 0 if argv else 2
    surface, rest = argv[0], argv[1:]
    entry = SURFACES.get(surface)
    if entry is None:
        print(f"unknown surface {surface!r}\n\n{_usage()}", file=sys.stderr)
        return 2
    return entry[0](rest, f"repro {surface}")


if __name__ == "__main__":
    sys.exit(main())
