"""The account of a run for one family of queries — those whose every
output row carries the timestamp of the one input event it answers (a
filter, a projection, a per-event window aggregate) — which the plain
references of such queries share. A reference of another family (a join, a
pattern, a window that emits expired rows) brings its own `account`,
`completed` and `expected_output_rows`; run.py asks the reference, never
this file.

Conservation over everything a run delivered: the smoke's seven checks,
streamed block by block so that a 40 s window's worth (10^8 rows) is checked
in seconds, and the event-level account that gives `failed`. Every event's
timestamp is its global index, so a bitmap over indexes says what came out,
and the generator says what should have.
"""

from __future__ import annotations

import numpy as np


class OneToOne:
    """`passes(cols, config, stream)` says which events of a generated
    frame the query answers with a row; `expected_rows(passed, config)` how
    many rows `passed` answered events give in all (full windows only, for
    a batch window)."""

    def __init__(self, passes, expected_rows) -> None:
        self.passes, self.expected_rows = passes, expected_rows

    def answered(self, run: dict, f: int) -> np.ndarray:
        events = run["events"]
        key = ("answered", events.source(f))
        mask = events.memo.get(key)
        if mask is None:
            mask = events.memo[key] = self.passes(
                events.frame_columns(f), run["config"],
                events.plan_of(f)["stream"])
        return mask

    def expected_output_rows(self, run: dict, sent_frames) -> int:
        """Rows the callback is owed once `sent_frames` are through."""
        passed = sum(int(np.count_nonzero(self.answered(run, f)))
                     for f in sent_frames)
        return self.expected_rows(passed, run["config"])

    def completed(self, run: dict, lo_ns: int, hi_ns: int) -> float:
        """Input events whose results reached the callback in [lo, hi):
        each measured frame is credited its rows times the share of its
        output rows delivered then (a frame whose results are all out
        counts whole)."""
        frames, delivered = run["frames"], run["delivered"]
        stride = run["events"].stride
        if not frames["frame"].size:
            return 0.0
        n_frames = int(frames["frame"].max()) + 1
        out_rows = np.zeros(n_frames, np.int64)
        t = delivered["enter_ns"]
        for i in np.nonzero((t >= lo_ns) & (t < hi_ns))[0].tolist():
            f = delivered["blocks"][i].timestamps // stride
            out_rows += np.bincount(f[(f >= 0) & (f < n_frames)],
                                    minlength=n_frames)
        credit = 0.0
        ok = frames["status"] == 200
        for f, rows in zip(frames["frame"][ok].tolist(),
                           frames["rows"][ok].tolist()):
            if out_rows[f]:
                passing = int(np.count_nonzero(self.answered(run, f)))
                credit += rows * min(1.0, out_rows[f] / max(passing, 1))
        return credit

    def account(self, run: dict) -> dict:
        """`run["frames"]` is the producers' merged log; `run["sent_extra"]`
        maps the frame numbers the parent itself posted (warm-up, closing)
        to the rows the server accepted. Returns the checks, the
        event-level account and the failures in words."""
        frames, events = run["frames"], run["events"]
        sent_extra, stats_end = run["sent_extra"], run["stats_end"]
        stride, n_prod = events.stride, events.producers
        ok200 = frames["status"] == 200
        sent_frames = np.concatenate([
            np.fromiter(sent_extra, np.int64, len(sent_extra)),
            frames["frame"][ok200]])
        accepted = int(sum(sent_extra.values())
                       + frames["accepted"][ok200].sum())
        n_frames = int(sent_frames.max()) + 1 if sent_frames.size else 0
        n_events = n_frames * stride

        should = np.zeros(n_events, bool)
        sent_by_stream = [0] * len(events.plans)
        for f in sent_frames.tolist():
            mask = self.answered(run, f)
            should[f * stride:f * stride + mask.size] = mask
            sent_by_stream[events.source(f)[0]] += mask.size
        sent_rows = sum(sent_by_stream)
        passed = int(np.count_nonzero(should))

        # producer of every frame number, for the order check: the parent's
        # own frames count as one more producer
        prod_of_frame = np.full(n_frames, n_prod, np.int64)
        prod_of_frame[frames["frame"][ok200]] = frames["producer"][ok200]
        last_ts = np.full(n_prod + 1, -1, np.int64)

        seen = np.zeros(n_events, bool)
        total_out = 0
        out_of_range = 0
        order_kept = True
        expired = False
        for block in run["delivered"]["blocks"]:
            ts = block.timestamps
            total_out += ts.size
            if ts.size == 0:
                continue
            expired = expired or bool(block.is_expired.any())
            inside = (ts >= 0) & (ts < n_events)
            if not inside.all():
                out_of_range += int(ts.size - np.count_nonzero(inside))
                ts = ts[inside]
            seen[ts] = True
            if ts[0] // stride == ts[-1] // stride and (
                    ts.size == 1 or bool(np.all(ts[1:] > ts[:-1]))):
                # the usual block: rising rows of one frame
                pieces = [(int(prod_of_frame[ts[0] // stride]), ts)]
            else:
                prod = prod_of_frame[ts // stride]
                pieces = [(p, ts[prod == p])
                          for p in np.unique(prod).tolist()]
            for p, mine in pieces:
                if mine[0] <= last_ts[p] or (mine.size > 1
                                             and np.any(np.diff(mine) <= 0)):
                    order_kept = False
                last_ts[p] = mine[-1]
        distinct = int(np.count_nonzero(seen))
        duplicates = total_out - out_of_range - distinct
        spurious = int(np.count_nonzero(seen & ~should)) + out_of_range
        missing_mask = should & ~seen
        missing = int(np.count_nonzero(missing_mask))

        pipes = stats_end.get("ingress_pipeline") or {}
        rows_in = [(pipes.get(plan["stream"]) or {}).get("rows_in")
                   for plan in events.plans]
        checks = {
            "accepted_equals_sent": accepted == sent_rows,
            "pipeline_rows_in_equals_sent": rows_in == sent_by_stream,
            "ingress_dropped_zero": not stats_end.get("ingress_dropped"),
            "rows_out_is_full_windows":
                total_out == self.expected_rows(passed, run["config"]),
            "no_duplicates": duplicates == 0,
            "only_sent_events_that_passed": spurious == 0,
            "producer_order_kept": order_kept,
            "no_expired_rows": not expired,
        }
        # the account over the measured frames alone (those the producers
        # logged)
        measured = np.zeros(n_frames, bool)
        measured[frames["frame"][ok200]] = True
        missing_measured = int(np.count_nonzero(
            missing_mask.reshape(-1, stride)[measured])) if n_events else 0
        refused = int(frames["rows"][~ok200].sum())
        return {
            "checks": checks,
            "conserved": all(checks.values()),
            "failures": [f"conservation check {k} failed"
                         for k, v in checks.items() if not v],
            "attempted": int(frames["rows"].sum()),
            "failed": refused + missing_measured + duplicates + spurious,
            "detail": {"sent_rows": sent_rows, "accepted": accepted,
                       "rows_in": rows_in, "passed": passed,
                       "rows_out": total_out, "missing": missing,
                       "duplicates": duplicates, "spurious": spurious,
                       "refused_events": refused},
        }
