"""Device step: of the lanes the window's chunks held in the measured
window, the share the selector ran over. A sliding window whose expiry
width exceeds its batch packs what arrived and what left at the front of a
chunk of batch + expiry-width lanes; the selector runs in rounds of one
batch over that prefix, or once over the whole chunk (100). Source: the
program's counters (`statistics_report()["windows"]`: `selector_lanes` over
`out_lanes`, as deltas). A program without the counter leaves nothing to
read."""


def read(run: dict):
    ran = held = 0
    for name, z in (run["stats1"].get("windows") or {}).items():
        a = (run["stats0"].get("windows") or {}).get(name)
        if a is None or "selector_lanes" not in z \
                or "selector_lanes" not in a:
            return None
        ran += z["selector_lanes"] - a["selector_lanes"]
        held += z["out_lanes"] - a["out_lanes"]
    if held <= 0:
        return None
    return 100.0 * ran / held
