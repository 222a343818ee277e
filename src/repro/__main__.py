"""``python -m repro`` — dispatch to the unified CLI (repro.cli)."""
import sys

from repro.cli import main

if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
