"""Reader kinds for per-layer metrics: one module per kind, each with
``read(spec, run, cell, values) -> float | None``. ``None`` means there was
nothing to read, and the metric is left out of the line."""


import importlib


def read_spec(spec: dict, run, cell, values: dict) -> float | None:
    """Dispatch on the spec's ``reader`` kind: the module of that name here."""
    kind = spec["reader"]
    if not kind.isidentifier():
        raise ValueError(f"bad reader kind {kind!r}")
    return importlib.import_module(f"{__name__}.{kind}").read(spec, run, cell, values)


def read_all(cell, run) -> dict:
    """Every per-layer metric of the cell, in the manifest's order, so a
    derived metric can use the ones before it and the end-to-end values."""
    from .. import measure

    values = dict(measure.end_to_end(run))
    for entry, spec in cell.per_layer:
        values[entry["name"]] = read_spec(spec, run, cell, values)
    return values
