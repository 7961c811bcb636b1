"""Where set-up went, from the program's own account
(``deepspeed_tpu.telemetry.setup_account()``: jax's trace / lower / compile /
cache-load durations booked to the step program whose first call paid them,
the engines' ``ds.engine_init`` spans, ``import_seconds``).  Only what was
booked before the window opened counts (``ctx["tracer"].t0``; a record's and a
span's ``host_ns`` is on ``time.perf_counter``'s clock, as ``t0`` is).  A
program without the account reads nothing.

``what``: ``trace`` / ``lower`` / ``compile`` / ``cache_load`` (seconds of
that part over every ``program_setup`` record, ``other`` too), ``step_programs``
(records of a step program: first calls of a program and shape),
``step_programs_s`` (the four parts booked to those), ``engine_init``
(seconds of the ``ds.engine_init`` spans), ``import`` (``import_seconds``)."""

PARTS = ("trace", "lower", "compile", "cache_load")


def account(ctx):
    if "_setup_account" not in ctx:
        try:
            from deepspeed_tpu.telemetry import setup_account
        except ImportError:             # a program from before the account
            setup_account = None
        ctx["_setup_account"] = setup_account() if setup_account else None
    return ctx["_setup_account"]


def read(ctx, spec):
    acc = account(ctx)
    if acc is None:
        return None
    t0 = getattr(ctx.get("tracer"), "t0", None)
    before = [x for x in acc["records"] + acc["init_spans"]
              if t0 is None or x["host_ns"] < t0 * 1e9]
    records = [r for r in before if "program" in r]
    steps = [r for r in records if r["program"] != "other"]
    what = spec["what"]
    if what in PARTS:
        return sum(r[f"{what}_s"] for r in records)
    if what == "step_programs":
        return len(steps)
    if what == "step_programs_s":
        return sum(r[f"{p}_s"] for r in steps for p in PARTS)
    if what == "engine_init":
        return sum(s["seconds"] for s in before
                   if s.get("part") == "engine_init")
    if what == "import":
        return acc["import_seconds"]
    raise KeyError(what)
