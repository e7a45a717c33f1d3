"""The zoo cells' load generator: `loadgen.py` over zoo tenants.

    python bench/harness/zoo_loadgen.py '<spec json>'

The same child, line protocol, traffic and judgement as `loadgen.py`; its
reference resolves each tenant name `<dataset>_v<k>` to the dataset's
readings and the draw's own weights (`zoo_reference.py`).  Never touches
the chip.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]


def main(spec: dict) -> None:
    sys.path[:0] = [str(BENCH_DIR), str(BENCH_DIR.parent / "src")]
    import harness
    from harness import loadgen, zoo_reference

    # `loadgen.main` takes its reference as `from harness import reference`
    harness.reference = zoo_reference
    loadgen.main(spec)


if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    main(json.loads(sys.argv[1]))
