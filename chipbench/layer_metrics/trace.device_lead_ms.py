"""How far the device's clock runs ahead of the host's in this trace, at least
(negative: behind): the largest, over the jobs, of the end of the device's last
operation of a job minus srml.fit.wait's end.  Every number that joins the two
clocks (the idle times per step) can be off by as much as the lead.  The upper
bound (the device's first operation of the next job against its
srml.fit.init's opening) goes on the run's DETAIL line beside it."""
from chipbench.step_spans import device_lead_bounds_ms


def read(ctx):
    bounds = device_lead_bounds_ms(ctx.trace)
    if bounds is None:
        return None
    if isinstance(ctx.detail, dict):
        ctx.detail["device_lead_ms"] = {"lower": bounds[0], "upper": bounds[1]}
    return bounds[0]
