"""Share of the query rows the serving path padded: 100 (1 - sum of ``nq``
/ sum of ``bucket``) over the program's ``repro.serve.prepare`` spans, whose
args count a request's points and the bucket it was padded to. Layer:
serving host path (``serve/assign.py``). Moves ``assign_points_per_s``."""


def read(run):
    spans = run.trace.program_named("serve.prepare") if run.trace else []
    bucket = sum(s.args.get("bucket", 0) for s in spans)
    if not bucket:
        return None
    return 100.0 * (1.0 - sum(s.args.get("nq", 0) for s in spans) / bucket)
