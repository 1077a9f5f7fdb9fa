"""Host milliseconds per query run in the program's ``pipeline.plan`` span
(``plan_search`` of the run), self time, from the spans of the traced
window."""


def read(cell):
    spans = cell.layer.get("spans")
    runs = cell.layer.get("runs")
    if not spans or not runs:
        return None
    plan = [e for e in spans if e.name == "pipeline.plan"]
    if not plan:
        return None
    total = 0
    for p in plan:
        inner = sum(c.t_end_ns - c.t_start_ns for c in spans
                    if c is not p and c.tid == p.tid
                    and p.t_start_ns <= c.t_start_ns
                    and c.t_end_ns <= p.t_end_ns)
        total += p.t_end_ns - p.t_start_ns - inner
    return total / 1e6 / runs
