"""The mesh planner's ``PlanService.resolve_mesh`` in set-up (host clock)."""


def read(r):
    return r.plan_s * 1e3
