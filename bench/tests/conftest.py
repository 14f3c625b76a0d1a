"""Tests of the benchmark's own code, on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="session")
def tiny():
    """Small stand-ins of both configurations (the program's models at
    a few dozen features), for runs on the CPU."""
    return {name: json.loads((DATA / f"{name}.json").read_text())
            for name in ("tiny-qwen3", "tiny-stablelm")}
