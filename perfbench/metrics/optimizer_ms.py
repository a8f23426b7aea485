"""Own device ms per step of the ops in the program's `optimizer` scope
(the AdamW update with its grad norm), mean over chips; from the trace
and the executable's scope map (`yardstick.trace.scope_ms`)."""
from yardstick import trace


def read(t):
    return trace.scope_ms(t.rec, t.lo, t.hi, t.n_steps, ["optimizer"])
