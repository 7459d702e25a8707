"""Run one cell's reference once: the last line of stdout is one JSON object
with ``loss``, or with ``skipped`` or ``error`` and the reason."""

from __future__ import annotations

import argparse
import importlib
import json
import os
import re
import sys
import time
from pathlib import Path

from .. import data, flops, manifest

# What a CPU is given to do: a rehearsal's tiny cell, never a cell's real size.
CPU_FLOP_LIMIT = 2e11


def main(argv: list[str] | None = None) -> int:
    t0 = time.monotonic()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--root", type=Path, default=manifest.ROOT)
    args = parser.parse_args(argv)
    cell = manifest.resolve(args.workload, args.root)
    name = cell.config["checks"]["reference"]
    if not re.fullmatch(r"[A-Za-z0-9_]+", name):
        raise SystemExit(f"perfbench.reference: {name!r} is no module name")
    module = importlib.import_module(f"{__package__}.{name}")

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):  # the program's own rule (hw.py)
        jax.config.update("jax_compilation_cache_dir", str(args.root / ".jax_cache"))
    platform = jax.default_backend()
    ids = data.first_batch(cell.traffic, args.seed)
    out = {"platform": platform, "rows": int(ids.shape[0]), "sequence": int(ids.shape[1])}
    forward = flops.flops_per_token(cell.config["flops"], ids.shape[1]) / 3 * ids.size
    if platform == "cpu" and forward > CPU_FLOP_LIMIT:
        out["skipped"] = f"{forward:.3g} FLOP of forward pass is no work for a CPU"
    else:
        out["loss"] = module.first_loss(cell.config, ids, data.model_seed(args.seed))
    out["seconds"] = time.monotonic() - t0
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
