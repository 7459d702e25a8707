"""The one traffic generator: counting sequences from the seed.

A traffic file's ``data`` group gives the parameters; the seed only moves
where each sequence starts, so every seed gives the same shapes and the same
amount of work. The data set holds more sequences than the fastest plausible
run can consume (the traffic file says how many), so no run measures a
wrap-around.
"""

from __future__ import annotations

from pathlib import Path


def slices(traffic: dict, seed: int):
    """The data set's slices in order, each ``[rows_per_slice, sequence]``."""
    import numpy as np

    spec = traffic["data"]
    if spec["generator"] != "counting":
        raise ValueError(f"unknown data generator {spec['generator']!r}")
    rows, seq, mod = spec["rows_per_slice"], traffic["sequence"], spec["modulus"]
    rng = np.random.default_rng(seed)
    for _ in range(-(-spec["sequences"] // rows)):
        starts = rng.integers(0, mod, (rows, 1))
        yield ((starts + np.arange(seq)) % mod).astype(np.int32)


def write_dataset(directory: Path, traffic: dict, seed: int) -> int:
    """Write the slices; returns the number of sequences."""
    from safetensors.numpy import save_file

    directory.mkdir(parents=True, exist_ok=True)
    count = 0
    for i, ids in enumerate(slices(traffic, seed)):
        save_file({"input_ids": ids}, str(directory / f"slice_{i:04d}.safetensors"))
        count += len(ids)
    return count


def first_batch(traffic: dict, seed: int):
    """The rows of the worker's first step: the scheduler hands a job's
    first request slice 0 (``SliceTracker.next``: the lowest free index),
    the worker reads a slice's rows in order and its first batch is the
    first ``batch`` of them (``tests/perfbench/test_reference.py`` holds the
    program to that). A reference computes round 0's first loss on these."""
    batch = traffic["batch"]
    if batch > traffic["data"]["rows_per_slice"]:
        raise ValueError("the first batch would span two slices")
    return next(slices(traffic, seed))[:batch]


def model_seed(seed: int) -> int:
    """``job.model_seed`` of a run: the key the worker's ``model.init`` gets."""
    return seed % 2**31


def job_sets(config: dict, traffic: dict, seed: int) -> list[str]:
    """The job as ``scheduler run --set`` strings: the configuration's model,
    the mix's round shape, and a round count the window never reaches."""
    batch, steps = traffic["batch"], traffic["inner_steps"]
    return [
        "job.dataset=counting",
        "job.model_type=causal-lm",
        *config["job_sets"],
        f"job.model_seed={model_seed(seed)}",
        "job.update_rounds=100000",
        "job.num_workers=1",
        # The auction sizes the batch as offered/required chips: one chip
        # sold whole to a job asking 1/batch of a chip per sample.
        f"job.worker_tpu={1.0 / batch!r}",
        f"job.max_batch_size={batch}",
        f"job.avg_samples_between_updates={batch * steps}",
        *traffic.get("job_sets", []),
    ]
