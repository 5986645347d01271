"""The program's own table of compiled programs
(``mxnet_tpu.tracing.programs()``), as the per-layer readers use it:
what each program is (its role), what it cost to get, and which
component of the model each instruction of the training step belongs
to.

Every function works on plain data handed in (a list of records with
``module``, ``role``, ``built()``, ``seconds()``, ``scopes()``; a
reduction's ``ops`` and ``programs`` tables), so the tests check them
on a table made by hand; only ``table`` touches the program.

A program without the table (an older commit) gives ``table() is
None``: every reader then returns None and the line leaves its metric
out.
"""
import re
import sys
import time

UNSCOPED = "unscoped"
# a reduction's op names are ``fusion.12`` or ``name[custom call target]``
_TARGET = re.compile(r"\[[^\]]*\]$")
_CONTROL = re.compile(r"^(while|call|conditional)(\.\d+)?$")
# what an admission runs on the device beside its prefill
ADMISSION_ROLES = ("prefill", "cache_write", "cache_install", "select")


def _say(text):
    sys.stderr.write(f"chipbench: programs: {text}\n")


def table():
    """The program's table, or None where it has none."""
    from mxnet_tpu import tracing
    programs = getattr(tracing, "programs", None)
    return None if programs is None else programs()


def stage_seconds(programs, *stages):
    """Seconds of ``stages`` over the records WITH a role (the
    benchmark's reference programs and eager initialisers have none),
    builds the table's own reading caused left out; None without a
    table or without such a record."""
    if programs is None:
        return None
    with_role = [p for p in programs if p.role is not None and p.built()]
    if not with_role:
        return None
    return sum(p.seconds(*stages) for p in with_role)


def modules_of(programs, roles):
    """The module names ("XLA Modules" line) of the given roles."""
    return {p.module for p in programs if p.role in roles}


def device_ms(reduction, programs, roles, per_role):
    """Milliseconds the modules of ``roles`` held the device in the
    traced stretch, a run of the modules of ``per_role``; None where
    none of those ran."""
    if reduction is None or programs is None:
        return None
    ran = reduction["programs"]
    secs = sum(ran[m][0] for m in modules_of(programs, roles) if m in ran)
    runs = sum(ran[m][1] for m in modules_of(programs, (per_role,))
               if m in ran)
    return 1e3 * secs / runs if runs > 0 and secs > 0 else None


def by_component(ops, scopes):
    """{component: seconds} of a reduction's ``ops`` {name: [seconds,
    count]} under ``scopes`` {instruction: (component, part,
    direction)}: an op the program does not have, or has without a
    vocabulary word, is ``unscoped``; a ``while``, ``call`` or
    ``conditional`` the program does not list as a leaf encloses its
    children on the trace's line and is left out."""
    out = {}
    for name, (secs, _) in ops.items():
        name = _TARGET.sub("", name)
        if name not in scopes and _CONTROL.match(name):
            continue
        comp = scopes[name][0] if name in scopes else UNSCOPED
        out[comp] = out.get(comp, 0.0) + secs
    return out


def step_components(ctx, role="train_step"):
    """``by_component`` of the traced run's ops under the scopes of the
    newest program of ``role``, plus ``"shared"``: the device seconds
    of every OTHER program run in the stretch, an upper bound of what
    they may have put under the step's instruction names (the reduction
    keys device time by the instruction's name alone).  Computed once a
    run (kept in ``ctx``); None without a trace, a table or the
    program."""
    if "step_components" in ctx:
        return ctx["step_components"]
    ctx["step_components"] = None
    red, programs = ctx["reduction"], table()
    if red is None or programs is None:
        return None
    steps = [p for p in programs if p.role == role and p.built()]
    if not steps:
        return None
    t = time.perf_counter()
    scopes = steps[-1].scopes()
    _say(f"scopes of {steps[-1].module} read in "
         f"{time.perf_counter() - t:.3f} s: "
         f"{None if scopes is None else len(scopes)} instructions")
    if scopes is None:
        return None
    found = by_component(red["ops"], scopes)
    found["shared"] = sum(secs for module, (secs, _)
                          in red["programs"].items()
                          if module != steps[-1].module)
    _say(f"device seconds by component: {found}")
    ctx["step_components"] = found
    return found


# the three components the training cell reports by name; ``.other`` is
# every other word of the vocabulary (embed, head, loss, a norm that
# stands alone, ...), never ``unscoped``
NAMED = ("attn", "ffn", "optim")


def component_ms_per_step(ctx, component):
    """Device milliseconds a traced step spends in ``component`` (one
    of NAMED, or ``"other"``)."""
    found, steps = step_components(ctx), ctx["readings"].get("traced_steps")
    if found is None or not steps:
        return None
    if component == "other":
        secs = sum(s for c, s in found.items()
                   if c not in NAMED + (UNSCOPED, "shared"))
    else:
        secs = found.get(component, 0.0)
    return 1e3 * secs / steps


def unscoped_pct(ctx):
    """100 x (unscoped + shared seconds) over all ops' seconds."""
    found = step_components(ctx)
    if found is None:
        return None
    total = sum(s for c, s in found.items() if c != "shared")
    if total <= 0:
        return None
    return 100.0 * min(total, found.get(UNSCOPED, 0.0)
                       + found["shared"]) / total
