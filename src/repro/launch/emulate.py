"""Emulated-FaaS training driver — thin shim over ``python -m repro emulate``.

The implementation moved to :mod:`repro.cli` when the unified deployment API
landed; this module stays so ``python -m repro.launch.emulate`` keeps
working.  Prefer:

    PYTHONPATH=src python -m repro emulate --model bert-large --batch 64
    PYTHONPATH=src python -m repro emulate plan.json --steps 2
    PYTHONPATH=src python -m repro emulate --numerics --model phi3-mini-3.8b@reduced4 \\
        --stages 2 --dp 2 --batch 8 --seq 16 --steps 2
"""
from __future__ import annotations

import sys
from typing import List, Optional

from repro.cli import main as _cli_main


def main(argv: Optional[List[str]] = None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    # the pre-API driver spelled the arch flag --arch; keep both forms working
    args = ["--model" if a == "--arch"
            else "--model=" + a[len("--arch="):] if a.startswith("--arch=")
            else a
            for a in args]
    return _cli_main(["emulate", *args])


if __name__ == "__main__":
    sys.exit(main())
