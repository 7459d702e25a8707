"""From the roles' logs to the cell's end-to-end metrics and ``correct``.

The unit is the whole round. The clock is the harness's own: the moment each
``round N done`` line arrived in the worker's log (``Run.arrivals``, read
every few milliseconds). Rounds are contiguous (``log_round`` restarts the
worker's mark at the moment it logs), so the span from round 0's close to
the last measured round's close is all the work and all the time. The tokens
are the cell's own arithmetic, H x batch x sequence a round, and a round that
logged other numbers fails a check. Only the time inside steps, which nothing
outside the worker can see, is the program's: ``median_step_s`` on its
``round N done`` line. A PR that changes how that field is computed moves
``sync_exposed_s`` (PERF.md 2).
"""

from __future__ import annotations

import math

from . import logs


def select_rounds(rounds: list[dict], arrivals: dict, seconds: float) -> list[dict]:
    """Round 1, and the rounds after it that closed within ``seconds`` of
    round 0's close. A round is the unit of work and is never cut: a window
    that ends inside round 1 runs on to that round's close (the harness
    waits for it), so a slower host gives a longer run and a lower rate, not
    a run with nothing in it. Each round gets ``wall`` (its own close minus
    the one before, harness clock)."""
    out = []
    for expect, r in enumerate(rounds[1:], start=1):
        if r["round"] != expect or expect not in arrivals or 0 not in arrivals:
            break  # a gap: nothing after it is contiguous
        if expect > 1 and arrivals[expect] - arrivals[0] > seconds:
            break
        out.append({**r, "wall": arrivals[expect] - arrivals[expect - 1]})
    return out


def next_round_fits(arrivals: dict, seconds: float) -> bool:
    """Whether one more round like the last could still close in the window."""
    last = max(arrivals)
    if last < 1:
        return True
    took = arrivals[last] - arrivals[last - 1]
    return arrivals[last] - arrivals[0] + 0.9 * took <= seconds


def h_by_rule(step_s: float, sync_s: float, seconds: float, share: float = 0.8) -> int:
    """Tentpole 5: the largest multiple of 8 with H*step + sync <= share*seconds."""
    return max(8, int((share * seconds - sync_s) / step_s) // 8 * 8)


def end_to_end(run) -> dict:
    """Values by metric name; a metric that cannot be computed is absent."""
    out: dict = {}
    if 0 in run.arrivals:
        out["setup_s"] = run.arrivals[0] - run.t_start
    m = run.measured
    if m:
        out["tokens_per_s"] = len(m) * run.round_tokens / sum(r["wall"] for r in m)
        # All the time of the window that was not inside a step, per round:
        # a stall in any one round shows.
        in_steps = sum(r["steps"] * r["median_step_s"] for r in m)
        out["sync_exposed_s"] = (sum(r["wall"] for r in m) - in_steps) / len(m)
    return out


def round_checks(run, r: dict) -> dict[str, bool]:
    """What must hold of one measured round. A round that breaks one of
    these counts among ``failed``."""
    outer = [o for o in run.outer if o["round"] == r["round"]]
    r0 = run.rounds[0]
    return {
        "work_as_the_cell_says": r["tokens"] == run.round_tokens
        and r["steps"] == run.round_steps,
        "one_delta_per_round": run.pushed.get(r["round"]) == 1,
        "one_outer_update_per_round": len(outer) == 1,
        "losses_finite": r["nonfinite"] == 0 and math.isfinite(r["loss_mean"]),
        # A recompile would cost what round 0's first step did.
        "no_recompile_in_window": r["first_step_s"] < 0.5 * r0["first_step_s"],
    }


def descent_sum(r0: dict) -> float:
    """The losses of round 0's steps after the first, summed: how fast the
    seeded model learns the counting data, first loss apart."""
    return r0["loss_mean"] * r0["steps"] - r0["loss_first"]


def margins(run, config: dict, traffic: dict) -> dict[str, dict]:
    """Every number that ``correct`` holds to a limit, under its check's
    name: ``value`` and the ``low`` and ``high`` it may not pass (``high`` is
    exclusive where ``strict``). A check whose number does not exist yet (no
    round 0, no measured round, a reference that did not run) is absent, and
    fails in ``checks``. The note ``checks`` carries them and stderr ends in
    them, so that a near miss is on record with its number."""
    out: dict[str, dict] = {}
    r0 = run.rounds[0] if run.rounds and run.rounds[0]["round"] == 0 else None
    c, bands, m = config["checks"], traffic.get("checks", {}), run.measured
    if r0 is None:
        return out
    if r0["first_step_s"] > 0 and m:
        out["no_recompile_in_window"] = {
            "value": max(r["first_step_s"] for r in m) / r0["first_step_s"],
            "high": 0.5, "strict": True, "of": "round 0's first_step_s"}
    if c.get("reference"):
        ref = (run.reference or {}).get("loss")
        if isinstance(ref, float) and math.isfinite(ref):
            out["first_loss_as_reference"] = {
                "value": abs(r0["loss_first"] - ref), "high": c["reference_tolerance"],
                "program": r0["loss_first"], "reference": ref}
    else:
        target = math.log(c["vocabulary"]) + c["loss_first_offset"]
        out["first_loss_near_ln_vocabulary"] = {
            "value": r0["loss_first"], "low": target - c["loss_first_tolerance"],
            "high": target + c["loss_first_tolerance"]}
    if m:
        # Round 1 and no later one: a faster program closes more rounds in a
        # window, and a limit recorded from round 1 says nothing of round 3
        # (``later_rounds`` has what those read, without a limit).
        out["loss_fell"] = {"value": m[0]["loss_mean"], "high": r0["loss_mean"],
                            "strict": True, "round": m[0]["round"]}
    band = bands.get("descent_after_first_step")
    if band:
        out["descent_as_recorded"] = {
            "value": descent_sum(r0), "low": band["low"], "high": band["high"]}
    share = bands.get("loss_first_after_outer_step_share")
    if share is not None and m:
        # The faults this names (an outer update not applied, or applied with
        # the wrong sign) put round 1's first loss back at round 0's or
        # above; a share of the run's own first loss needs no vocabulary.
        out["loss_stays_down_after_outer_step"] = {
            "value": m[0]["loss_first"], "high": share * r0["loss_first"],
            "share": share, "of": r0["loss_first"], "round": m[0]["round"]}
        if len(m) > 1:
            # From round 2 on a first loss may be anything (``later_rounds``),
            # but the round has to come back: a worker that an outer update
            # threw off and that stays off closes where it was thrown to.
            worst = max(m[1:], key=lambda r: r["loss_last"])
            out["later_rounds_close_down"] = {
                "value": worst["loss_last"], "high": share * r0["loss_first"],
                "share": share, "of": r0["loss_first"], "round": worst["round"]}
    return out


def later_rounds(run) -> dict:
    """Every measured round's first loss by round number, and the largest:
    on the ``checks`` note and on stderr without a limit. The limits on a
    first loss read round 0 and round 1; from round 2 on the outer step's
    momentum throws the worker off in some seeds for part of a round
    (PERF.md 7), which stays on record here until the program's own PR.
    What is held of those rounds is their closing loss
    (``later_rounds_close_down``)."""
    first = {r["round"]: r["loss_first"] for r in run.measured}
    if not first:
        return {}
    worst = max(first, key=first.get)
    return {"first_loss": first, "largest": first[worst], "largest_in_round": worst}


def inside(margin: dict) -> bool:
    v = margin["value"]
    if not math.isfinite(v) or v < margin.get("low", -math.inf):
        return False
    high = margin.get("high", math.inf)
    return v < high if margin.get("strict") else v <= high


def checks(run, config: dict, traffic: dict) -> dict[str, bool]:
    """Tentpole 1's conditions for ``correct``, each by name. The ones that
    compare a number are ``margins``' (set on ``run.margins``)."""
    m, r0 = run.measured, (run.rounds[0] if run.rounds else None)
    per_round = [round_checks(run, r) for r in m]
    outer = {o["round"]: o for o in run.outer}
    dev = run.device or {}
    held = run.margins = margins(run, config, traffic)

    def holds(name: str) -> bool:
        return name in held and inside(held[name])

    if config["checks"].get("reference"):
        # The reference replaces the band around ln V. One that could not
        # run fails under its own name, never under the comparison's.
        first_loss = {"reference_ran": "first_loss_as_reference" in held}
        if first_loss["reference_ran"]:
            first_loss["first_loss_as_reference"] = (
                holds("first_loss_as_reference") and r0["nonfinite"] == 0)
    else:
        first_loss = {"first_loss_near_ln_vocabulary":
                      holds("first_loss_near_ln_vocabulary") and r0["nonfinite"] == 0}
    # The mix's own bands on the losses (traffic file, ``checks``), taken from
    # runs on the chip over many seeds: not a reference, but what a step that
    # learns at another pace, or an outer update that throws the worker off,
    # leaves. A mix without a band is not held to one.
    bands = {"descent_after_first_step": "descent_as_recorded",
             "loss_first_after_outer_step_share": "loss_stays_down_after_outer_step"}
    later = {"later_rounds_close_down": holds("later_rounds_close_down")} \
        if "later_rounds_close_down" in held else {}  # a window of one round has none
    return {
        "rounds_measured": bool(m),
        "rounds_in_order": [r["round"] for r in run.rounds] == list(range(len(run.rounds))),
        **{name: bool(m) and all(p[name] for p in per_round)
           for name in ("work_as_the_cell_says", "one_delta_per_round",
                        "one_outer_update_per_round", "losses_finite",
                        "no_recompile_in_window")},
        "native_ps_and_codec": bool(m) and all(
            outer.get(r["round"], {}).get("native_kernels") is True
            and outer.get(r["round"], {}).get("native_cbor") is True for r in m
        ),
        **first_loss,
        "loss_fell": holds("loss_fell"),
        **{check: holds(check) for key, check in bands.items()
           if traffic.get("checks", {}).get(key) is not None},
        **later,
        "attention_is_compiled_flash": (run.attention or "").startswith(
            "pallas flash kernel, compiled"
        ),
        "only_w0_holds_the_chip": run.holders in ([], ["w0"]),
        "device_is_tpu": dev.get("platform") == "tpu" and run.holders == ["w0"],
        "no_role_died": run.cause is None,
    }


def from_logs(run, texts: dict[str, str], traffic: dict, seconds: float) -> None:
    """Fill ``run`` from the roles' log texts. A recorded log has no
    arrivals; the lines' own timestamps stand in for them."""
    w0, ps = texts.get("w0", ""), texts.get("ps", "")
    run.rounds = logs.rounds(w0)
    if not run.arrivals:
        run.arrivals = {r["round"]: r["t"] - run.t_wall for r in run.rounds if r["t"]}
    run.round_steps = traffic["inner_steps"]
    run.round_tokens = traffic["inner_steps"] * traffic["batch"] * traffic["sequence"]
    run.measured = select_rounds(run.rounds, run.arrivals, seconds)
    run.outer = logs.outer_steps(ps)
    run.pushed = logs.deltas_pushed(ps)
    run.device = logs.device(w0)
    att = logs.find_line(w0, r"attention path: ")
    run.attention = att.split("attention path: ", 1)[1] if att else None
    peaks = [r["peak_bytes"] for r in run.rounds if isinstance(r.get("peak_bytes"), int)]
    run.memory_peak_bytes = max(peaks) if peaks else None


def result(run, cell, trace: bool, layer_values: dict | None = None) -> dict:
    """The contract's last line. ``attempted`` is the rounds that closed in
    the window, and one more if the job was given up on; ``failed`` is those
    among them that broke a per-round check or never closed."""
    run.checks = checks(run, cell.config, cell.traffic)
    gave_up = 1 if run.cause is not None else 0
    broken = sum(not all(round_checks(run, r).values()) for r in run.measured)
    if trace:
        entries, values = [e for e, _ in cell.per_layer], layer_values or {}
    else:
        entries, values = cell.end_to_end, end_to_end(run)
    device = dict(run.device or {}, memory_peak_bytes=run.memory_peak_bytes)
    profile = run.profile if trace and run.profile else {}
    if trace:
        device["busy_s"], device["window_s"] = profile.get("busy_s"), profile.get("window_s")
    out = {
        "correct": all(run.checks.values()) and broken + gave_up == 0,
        "attempted": len(run.measured) + gave_up, "failed": broken + gave_up,
        "metrics": {
            e["name"]: {"value": values[e["name"]], "unit": e["unit"]}
            for e in entries if values.get(e["name"]) is not None
        },
        "device": device,
    }
    if profile.get("breakdown"):
        out["breakdown"] = profile["breakdown"]
    # Last in the line: each number compared, beside its limits.
    out["compared"] = {
        name: {k: held[k] for k in ("value", "low", "high") if k in held}
        for name, held in run.margins.items()
    }
    return out
