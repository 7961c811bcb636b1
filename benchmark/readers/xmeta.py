"""What ``jax.profiler.ProfileData`` leaves out of a ``.xplane.pb``: the
*metadata* of the device ops.

Each event of a chip's ``XLA Ops`` line points at an ``XEventMetadata`` whose
stats carry, per HLO instruction, ``tf_op`` (the jax name stack:
``jit(train_batch)/fwd_bwd/transpose(jvp(GPT))/backbone/block_0/MLP_0/dot_general``,
with every ``jax.named_scope`` the program put around the operation),
``source`` (file:line), ``hlo_category``, ``flops``, ``bytes_accessed``.
``ProfileData`` (jax 0.9.0) exposes event stats but not these, and two
programs in one trace reuse instruction names (``fusion.12``), so the events
are decoded here with their metadata ids.  A fusion has ONE ``tf_op``: its
root instruction's.

The protobuf is read from the wire format (``XSpace`` is small: five message
types, a dozen fields), so nothing beyond the standard library is imported;
the host plane's ``ds.*`` annotations, whose arguments are event stats, come
through ``ProfileData``.  Times are nanoseconds on the trace's one clock,
``line.timestamp_ns + offset_ps / 1000`` as ``xtrace`` has them.
"""

import struct

import xtrace

ANNOTATION_PREFIX = "ds."
META_STATS = ("tf_op", "source", "hlo_category")


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, bytes (a
    view) for a length-delimited or fixed-width field."""
    i, end = 0, len(buf)
    while i < end:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield tag >> 3, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _stat(buf, stat_names):
    """One XStat -> (name, value)."""
    name, value = None, None
    for f, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v)
        elif f == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif f in (3, 4):
            value = v
        elif f == 5:
            value = _text(v)
        elif f == 7:                      # a string interned as a stat name
            value = stat_names.get(v)
    return name, value


def _map_entry(buf):
    key = value = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _event_metadata(buf, stat_names):
    text, out = "", {}
    for f, v in _fields(buf):
        if f == 2:
            text = _text(v)
        elif f == 5:
            name, value = _stat(v, stat_names)
            if name in META_STATS:
                out[name] = value
    out["text"] = text
    out["name"] = xtrace.short_name(text)
    out["opcode"] = xtrace.opcode_of(text)
    return out


def _line(buf):
    name, t0, events = "", 0, []
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            t0 = v
        elif f == 4:
            events.append(v)
    if name not in (xtrace.OPS_LINE, xtrace.MODULES_LINE):
        return name, []
    out = []
    for ev in events:
        mid = off = dur = 0
        for f, v in _fields(ev):
            if f == 1:
                mid = v
            elif f == 2:
                off = v
            elif f == 3:
                dur = v
        out.append((mid, t0 + off / 1e3, t0 + (off + dur) / 1e3))
    out.sort(key=lambda e: e[1])
    return name, out


def _serialized(path):
    if path.endswith(".textproto"):
        from jax.profiler import ProfileData
        with open(path) as f:
            return ProfileData.text_proto_to_serialized_xspace(f.read())
    with open(path, "rb") as f:
        return f.read()


def device_ops(path):
    """{chip: {"ops": [(metadata id, start_ns, end_ns)], "modules":
    [(program, start_ns, end_ns)], "meta": {metadata id: {"name", "text",
    "opcode", "tf_op", "source", "hlo_category"}}}} of a trace file."""
    out = {}
    for f, plane in _fields(memoryview(_serialized(path))):
        if f != 1:
            continue
        name, lines, metas, stats = "", [], [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = _text(v)
            elif pf == 3:
                lines.append(v)
            elif pf == 4:
                metas.append(v)
            elif pf == 5:
                key, value = _map_entry(v)
                for sf, sv in _fields(value):
                    if sf == 2:
                        stats[key] = _text(sv)
        m = xtrace.DEVICE_PLANE.match(name)
        if not m:
            continue
        meta = {}
        for entry in metas:
            key, value = _map_entry(entry)
            meta[key] = _event_metadata(value, stats)
        dev = {"ops": [], "modules": [], "meta": meta}
        for raw in lines:
            lname, events = _line(raw)
            if lname == xtrace.OPS_LINE:
                dev["ops"] = events
            elif lname == xtrace.MODULES_LINE:
                dev["modules"] = [
                    (xtrace.module_name(meta[mid]["text"]), a, b)
                    for mid, a, b in events if mid in meta]
        out[int(m.group(1))] = dev
    return out


def annotations(path, prefix=ANNOTATION_PREFIX):
    """The program's own host spans (``SpanTracer.span`` ->
    ``TraceAnnotation("ds.<name>", **args)``), sorted by start:
    [{"name", "thread", "start_ns", "end_ns", "args"}]."""
    from jax.profiler import ProfileData
    if path.endswith(".textproto"):
        data = ProfileData.from_serialized_xspace(_serialized(path))
    else:
        data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name != xtrace.HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append({
                        "name": ev.name, "thread": f"{line.name}~{i}",
                        "start_ns": ev.start_ns,
                        "end_ns": ev.start_ns + ev.duration_ns,
                        "args": dict(ev.stats)})
    out.sort(key=lambda a: a["start_ns"])
    return out


def of_run(ctx):
    """The traced run's file, decoded once: {"devices", "annotations"}, or
    None where the run was not traced."""
    if "_xmeta" not in ctx:
        tracer = ctx.get("tracer")
        path = (xtrace.find_xplane(tracer.dir)
                if tracer is not None and getattr(tracer, "started_at", None)
                else None)
        ctx["_xmeta"] = (None if path is None else
                         {"devices": device_ops(path),
                          "annotations": annotations(path)})
    return ctx["_xmeta"]
