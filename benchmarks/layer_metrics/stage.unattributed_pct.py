"""Device step: of the operation time inside whole executions of the cell's
step programs, the share whose operations name no `siddhi.<stage>` scope
(stages.py): operations XLA made without metadata (the expansions of
`cumsum` / `cummax`, copies) and code outside every stage. The stage metrics
and this one add up to the programs' operation time. None as the stage
metrics."""
import stages


def read(run: dict):
    return stages.unattributed_pct(run)
