"""Ingress pipeline: of the string values the workers passed to the
extension's interning in the window, the share its byte-keyed table
resolved (`native/columnar.c` `intern_column`, probed with the interpreter
released) and not the dict behind it. Source: the counters
`intern_table_hits` over `intern_values`, as deltas; nothing to read from a
program that has no such counter or where nothing was interned."""
import layers


def read(run: dict):
    hits = values = 0
    for a, z in zip(layers.pipelines(run["stats0"], run),
                    layers.pipelines(run["stats1"], run)):
        if "intern_values" not in a or "intern_values" not in z:
            return None
        hits += z["intern_table_hits"] - a["intern_table_hits"]
        values += z["intern_values"] - a["intern_values"]
    return 100.0 * hits / values if values > 0 else None
