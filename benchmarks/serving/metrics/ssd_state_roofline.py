"""Kernels: least time of the traced steps' recurrent-state traffic at HBM bandwidth over the device time of the ops that read or write the SSD state store (%).

Per traced step, each live row's float32 SSD state and conv taps are read
once and written once in every SSD layer (`ssd_bytes_per_row` of the
config's reference). The ops are found by an operand of the store's
shape: [layers, slots, heads, head channels, state] or its first two axes
flattened, where a slot is one of the cell's rows or the trash slot.
Control flow (a loop or a conditional whose event spans its body) is not
an op of its own: its body's ops count. None where the reference has no
SSD state or no op touched the store.
"""
import re

from serving.readers import _traced_steps

CONTROL_FLOW = re.compile(r"(while|conditional|call)(\.\d+)?$")


def read(ctx):
    ref, cfg, red = ctx["reference"], ctx["config"], ctx.get("trace")
    steps = _traced_steps(ctx)
    if not steps or not red or not hasattr(ref, "ssd_bytes_per_row"):
        return None
    if any(s.live is None for s in steps):
        return None
    layers = ref.ssd_layers(cfg)
    slots = int(ctx["cell"]["max_active"]) + 1
    h, p, n = ref.ssd_state_shape(cfg)
    store = re.compile(rf"\[(?:{layers},{slots}|{layers * slots}),"
                       rf"{h},{p},{n}\]")
    took = sum(sec for name, sec in red.get("op_s", {}).items()
               if not CONTROL_FLOW.match(name)
               and store.search(red["op_detail"].get(name, name)))
    if not took:
        return None
    moved = sum(2 * s.live * ref.ssd_bytes_per_row(cfg) for s in steps)
    return 100.0 * moved / ctx["peaks"]["hbm_bytes_per_s"] / took
