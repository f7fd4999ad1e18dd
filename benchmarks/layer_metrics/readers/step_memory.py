"""Where one chip's memory goes while the step runs, from the program's
own account of its bytes
(``deepspeed_tpu.telemetry.memory.step_memory``): the engine's state on
its fullest device counted by its shards where it was placed, the
executable's ``memory_analysis()`` (XLA states it per device), the summed
gradient tree as the step laid it out while it was traced, and the
allocator's peak on the fullest device — counts, no trace and no host
callback; what they leave of the allocator's reading is ``unaccounted``.
params:
  program: the name the program registered its step under
  field:   a dotted path into the account (``state.params``), or a list
           of them whose values are added
The value is in GiB (2**30 bytes) and may be negative (``unaccounted``).
None where the program has no such account (a commit from before it, or
no step has run) or the account holds None there (a CPU rehearsal:
``unaccounted`` on a backend whose allocator reports nothing,
``workspace`` on one whose ``memory_analysis()`` states no peak that
covers temporaries — the account has one definition of each and no
stand-in); raises where there is an account and it has no such field."""
from layer_metrics.readers.step_phase import BrokenJoin


def lookup(account, path):
    at = account
    for key in path.split("."):
        if not isinstance(at, dict) or key not in at:
            raise BrokenJoin(f"the step's account of its memory has no "
                             f"{path!r} (at {key!r}): {sorted(account)}")
        at = at[key]
    return at


def gib(account, field):
    values = [lookup(account, path)
              for path in ([field] if isinstance(field, str) else field)]
    if any(v is None for v in values):
        return None
    return float(sum(values)) / 2 ** 30


def read(ctx, params):
    try:
        from deepspeed_tpu.telemetry.memory import step_memory
    except ImportError:
        return None             # a program from before the account
    account = step_memory(params["program"])
    if account is None:
        return None
    return gib(account, params["field"])
