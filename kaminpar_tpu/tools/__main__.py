"""Tool multiplexer: ``python -m kaminpar_tpu.tools <tool> [args]``."""

from __future__ import annotations

import sys

from . import tools


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print("usage: python -m kaminpar_tpu.tools <tool> [args]")
        print("tools:", ", ".join(sorted(tools.REGISTRY)))
        return 0
    name, rest = argv[0], argv[1:]
    if name not in tools.REGISTRY:
        print(f"unknown tool '{name}'; available: {sorted(tools.REGISTRY)}")
        return 1
    return tools.REGISTRY[name](rest)


if __name__ == "__main__":
    raise SystemExit(main())
