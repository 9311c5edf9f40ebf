#!/usr/bin/env python
"""Fastpath zero-regression gate (SL204) over an inventory of in-tree apps.

Every compiled step of every app in `APPS` is certified against pjit's
C++ dispatch fastpath via `analysis.jaxpr_pass.fastpath_certify`: no host
callback, no ordered effect. KNOWN_VETOED is EMPTY — every sort of these
apps' steps runs on the device (ops/search.py) — and the gate is hard: ANY
vetoed step in ANY app of the inventory fails CI outright. A host callback
in a step would also make the plan superstep-ineligible
(core/superstep.py), so this gate doubles as the superstep-eligibility
floor for the inventory.

    python tools/fastpath_gate.py [--json]

Exit codes: 0 = all steps certified, 1 = any step is vetoed.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# the gate's own inventory (tools/cost_calibrate.py prices the same apps):
# a filter, a group-by, a distinct count, a pattern, a join, an event-time
# gate, the served ingress app and a sharded plane, one SiddhiQL text each
APPS = {
    "filter": """
    define stream TradeStream (symbol string, price double, volume long);
    @info(name = 'bench')
    from TradeStream[700.0 > price]
    select symbol, price
    insert into OutStream;
    """,
    "groupby": """
    define stream TradeStream (symbol string, price double, volume long);
    @info(name = 'bench')
    from TradeStream#window.lengthBatch(10000)
    select symbol, sum(price) as total, avg(price) as avgPrice
    group by symbol
    insert into SummaryStream;
    """,
    "distinct": """
    define stream TradeStream (symbol string, price double, volume long);
    @info(name = 'bench')
    from TradeStream#window.time(60 sec)
    select distinctCount(symbol) as distinctSymbols
    insert into OutStream;
    """,
    "pattern": """
    define stream StreamA (val int);
    define stream StreamB (val int);
    @info(name = 'bench')
    from every a=StreamA -> b=StreamB[b.val == a.val] within 5 sec
    select a.val as aVal, b.val as bVal
    insert into OutStream;
    """,
    "join": """
    define stream LeftStream (k int, v double);
    define stream RightStream (k int, v double);
    @info(name = 'bench')
    from LeftStream#window.length(100000) as a
    join RightStream#window.length(100000) as b
    on a.k == b.k
    select a.k as k, a.v as lv, b.v as rv
    insert into OutStream;
    """,
    "disorder": """
    @app:name('Disorder')
    @app:eventTime(timestamp='ts', allowed.lateness='50')
    define stream TradeStream (ts long, v long);
    @info(name = 'bench')
    from TradeStream select ts, v insert into OutStream;
    """,
    "e2e_ingress": """
    @app:name('IngressBench')
    @app:slo(stream='TradeStream', p99.ms='60000')
    @Async(buffer.size='8192', workers='2')
    define stream TradeStream (symbol string, price double, volume long);
    @info(name = 'filt')
    from TradeStream[price < 700.0]
    select symbol, price, volume
    insert into MidStream;
    @info(name = 'agg')
    from MidStream#window.lengthBatch(10000)
    select symbol, sum(price) as total, avg(price) as avgPrice
    group by symbol
    insert into SummaryStream;
    """,
    # the sharded execution plane's app: a key-local pipeline — windowless
    # running aggregate grouped by the partition key — replicated per shard
    # behind the partition-key router
    "sharded_e2e": """
    @app:name('ShardedBench')
    @app:shards(n='4', key='symbol')
    @Async(buffer.size='8192', workers='2')
    define stream TradeStream (symbol string, price double, volume long);
    @info(name = 'filt')
    from TradeStream[price < 700.0]
    select symbol, price, volume
    insert into MidStream;
    @info(name = 'agg')
    from MidStream
    select symbol, sum(price) as total, count() as n
    group by symbol
    insert into SummaryStream;
    """,
}

#: accepted vetoes, keyed "<app>:<step>". EMPTY by design — adding an
#: entry here requires a written justification next to it, and note that
#: any entry also forfeits superstep eligibility for its plan.
KNOWN_VETOED: dict = {}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--json", action="store_true", dest="as_json")
    args = ap.parse_args(argv)

    from siddhi_tpu.analysis.jaxpr_pass import fastpath_certify

    results: dict = {}
    regressions = []
    for app_name, text in APPS.items():
        verdicts = fastpath_certify(text)
        if not verdicts:
            regressions.append(f"{app_name}: no steps traced")
        for step, v in verdicts.items():
            key = f"{app_name}:{step}"
            results[key] = v
            if not v["certified"] and key not in KNOWN_VETOED:
                regressions.append(f"{key}: {'; '.join(v['vetoes'])}")

    if args.as_json:
        print(json.dumps({"steps": results,
                          "regressions": regressions}, indent=2))
    else:
        n_cert = sum(1 for v in results.values() if v["certified"])
        print(f"fastpath gate: {n_cert}/{len(results)} steps certified, "
              f"{len(regressions)} regression(s)")
        for r in regressions:
            print(f"REGRESSION {r}")
    return 1 if regressions else 0


if __name__ == "__main__":
    raise SystemExit(main())
