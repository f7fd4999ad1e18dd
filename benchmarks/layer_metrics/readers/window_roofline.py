"""A windowed attention kernel's share of its compute roofline: as
step_kernel_roofline, but the required work counts, for each query, the
keys inside its document AND its window — which S_eff (twice the mean keys
a query attends over under the causal mask alone) cannot say.  The count
is taken over the same fixed sample of the traffic file's own length
distribution that ``datagen.effective_context`` draws (4,096 rows, never
the run's seed): ``2 * sum over spans of sum_{i=1..len} min(i, window) /
sum of len``, handed to the function named under ``flops`` in S_eff's
place.
params:
  program, module, include, kernels, flops, passes: as
      step_kernel_roofline
  window: the key of the configuration's ``model`` block that holds the
      window
None where the program's map has no kernel of these names (a commit from
before the windowed kernels) or as step_phase does; raises as it does."""
import numpy as np

from harness import datagen
from layer_metrics.readers import step_kernel_roofline, step_phase


def keys_times_two(traffic, window):
    """Twice the mean number of keys a query must attend over."""
    seq_len = traffic["seq_len"]
    if not traffic["segment_ids"]:
        lens = np.array([seq_len], np.float64)
    else:
        documents = datagen.Documents(np.random.default_rng(0),
                                      traffic["documents"])
        lens = np.array([n for _ in range(4096)
                         for n, _ in documents.row(seq_len)], np.float64)
    short = np.minimum(lens, window)
    # sum_{i=1..n} min(i, w) = m (m + 1) / 2 + w (n - m), m = min(n, w)
    keys = short * (short + 1) / 2 + window * (lens - short)
    return float(2.0 * keys.sum() / lens.sum())


def read(ctx, params):
    table = step_phase.program_map(ctx, params)
    if table is None:
        return None
    if not any(row["kernel"] in params["kernels"] for row in table.values()):
        return None
    windowed = keys_times_two(ctx["traffic"], ctx["model"][params["window"]])
    return step_kernel_roofline.read({**ctx, "s_eff": windowed}, params)
