"""What the step's windowed flash calls visit, as the program states it
about itself while its step is traced
(``deepspeed_tpu.telemetry.tracing.flash_calls``: one row per shape of
call, a windowed call's with its ``window``, its ``blocks`` and the key
tiles a q-block visits) — no trace, no host callback.
params:
  program: the name the program registered its step under
The value is the keys a query's q-block visits in the forward and dq
loops, ``k_tiles_per_q_block * block_k``, of the windowed call with the
most heads: a constant of the window and the blocks, not a timing; what it
exceeds the required keys by (attention.window_flash_*_roofline counts
those) is masked work.  None where the program has no such account or no
windowed call (a commit from before them)."""


def read(ctx, params):
    try:
        from deepspeed_tpu.telemetry.tracing import flash_calls
    except ImportError:
        return None
    rows = [r for r in flash_calls(params["program"]) or ()
            if r.get("window") is not None]
    if not rows:
        return None
    row = max(rows, key=lambda r: r["heads"])
    return float(row["k_tiles_per_q_block"] * row["blocks"][1])
