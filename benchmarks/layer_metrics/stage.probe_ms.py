"""Device step: device ms per execution of the cell's step programs spent in
operations traced inside `siddhi.probe` (stages.py: the program's
`jax.named_scope`, read from the trace's `op_name`). None off the chip,
without a trace, from unscoped programs (built before PR 34, or loaded from a
compile cache such a build wrote) and in a cell whose step programs have no
such stage."""
import stages


def read(run: dict):
    return stages.stage_ms(run, "probe")
