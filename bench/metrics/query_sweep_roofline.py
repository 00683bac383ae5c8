"""Share of the roofline of the query sweep executable (``_execute``): the
bytes one batched sweep must read (``bench.costs.query_sweep_bytes``) times
the batches run, at peak HBM bandwidth, over their device time (%)."""
from bench.costs import query_sweep_bytes, query_sweep_flops, roofline_share
from bench.layers import module_runs, module_seconds


def read(run):
    s = module_seconds(run, "jit__execute")
    n = module_runs(run, "jit__execute")
    if not s or not n or run["peaks"] is None:
        return None
    sh = run["shapes"]
    args = (sh["n_slots"], sh["embed_dim"], sh["query_batch"])
    return roofline_share(n * query_sweep_bytes(*args),
                          n * query_sweep_flops(*args), s, run["peaks"])
