"""Run as ``python -m pytest bench/tests`` from the repository root; the
repository's own suite (``tests/``) leaves these out."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def pytest_configure(config):
    # programs compiled here are for this machine's CPU; keep them out
    # of the checkout's persistent cache, which a chip run reads
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
