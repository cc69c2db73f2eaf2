"""Small cells for the benchmark's CPU tests: the real configuration,
traffic and limits files, with the graph cut to 2,048 nodes (16 blocks),
the widths cut, RSC's budget raised so that every layer keeps some blocks
at these widths, and the SpMM on the jnp lowering, so that a whole run
takes seconds."""
import spec


def small(workload: str, **config) -> spec.Cell:
    cell = spec.load_cell(workload)
    cell.config.update(nodes=2048, classes=8, feat_dim=16, hidden=32,
                       backend="jnp", **config)
    cell.traffic.update(epochs=20, budget=0.5)
    return cell
