"""Kernel: the ``moe_gmm`` grouped expert products' device time inside the
fused decode steps (``jit_decode_step_*`` executions, not prefill) of the
traced slice, against their required work, in % of their roofline.

A fused step makes three calls per MoE layer (gate, up, down), so the
required work of one call is the architecture's ``expert_work`` at the
routing counters' mean per fused step (``RuntimeStats.moe_assignments``,
``moe_active_experts`` over ``n_decode_steps``, read before the slice)
over the step's calls; the share is the calls seen in the trace at that
work over their device time.  Nothing to read, and so no value, where
the program keeps no routing counters or the kernel did not run."""

from bench import spec

KERNEL = "moe_gmm_pallas"
CALLS_PER_LAYER = 3          # gate, up, down


def _inside(ev, spans) -> bool:
    return any(s.plane == ev.plane and s.start <= ev.start
               and ev.start + ev.dur <= s.start + s.dur for s in spans)


def read(run):
    c = getattr(run.impl, "counters", None) or {}
    steps = c.get("n_decode_steps")
    if not steps or "moe_assignments" not in c:
        return None
    tm = run.trace_mod
    steps_in = tm.modules(run.trace.events, "jit_decode_step_")
    evs = [e for e in tm.kernel_events(run.trace.events, KERNEL)
           if _inside(e, steps_in)]
    if not evs:
        return None
    cfg = run.cell.config
    calls = steps * CALLS_PER_LAYER * cfg["num_hidden_layers"]
    w = spec.arch(cfg).expert_work(cfg, c["moe_assignments"] / calls,
                                   c["moe_active_experts"] / calls)
    return 100.0 * len(evs) * run.work_floor(w) / sum(e.dur for e in evs)
