"""Program-identity checker suite (flylint v2, docs/static-analysis.md
"Program identity").

Three layers, mirroring tests/test_flylint.py:

1. **Rule fixtures** — a positive trip, a negative pass, and a
   suppression case per rule (`program-key-incomplete`,
   `program-key-overspecified`, `program-key-drift`,
   `jax-retrace-hazard`) against purpose-built mini compose/batcher
   trees in tmp_path.
2. **Real-file mutations** — the acceptance gate: a verbatim copy of
   `ops/compose.py` + `runtime/batcher.py` scans clean, and deleting
   `band_taps` from any ONE of the three identity systems (batched
   program-cache key, submit() group key, plan_descriptor) is caught as
   `program-key-drift` naming the component.
3. **Regression pins** for the real findings this PR fixed:
   `plan_descriptor` now serializes `pad_offset` and the fill
   `background` (two distinct extent/rotate programs must never share a
   descriptor), and the two deliberate exact-frame branches carry
   written `jax-retrace-hazard` suppressions (the repo-scans-clean gate
   in test_flylint.py holds everything else).
"""

import os
import textwrap

from tools.flylint.checkers.program_identity import ProgramIdentityChecker
from tools.flylint.core import Project, run_checkers

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write(root, relpath, text):
    path = os.path.join(str(root), relpath)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as fh:
        fh.write(textwrap.dedent(text))
    return path


def _scan(root, paths=("flyimg_tpu",)):
    project = Project(str(root), list(paths))
    return run_checkers(project, [ProgramIdentityChecker()], {})


def _rules(result):
    return {f.rule for f in result.findings}


def _messages(result, rule):
    return [f.message for f in result.findings if f.rule == rule]


# ---------------------------------------------------------------------------
# mini compose/batcher fixture: one factory, two builders, a group key,
# a descriptor, and a device_plan normalizer — complete by construction


_OPS_CLEAN = """\
    def make_program_fn(resample_out, pad_canvas, plan, band_taps=None):
        def program(image):
            out = resample(image, resample_out, band_taps)
            out = pad(out, pad_canvas)
            return finish(out, plan.quality_flag)
        return program


    class TransformPlan:
        def device_plan(self):
            return replace(self, crop=None, background=None)


    def plan_descriptor(plan, *, in_shape=None, resample_out=None,
                        pad_canvas=None, band_taps=None):
        desc = {"resample_out": resample_out, "pad_canvas": pad_canvas}
        desc["band_taps"] = band_taps
        desc["quality_flag"] = plan.quality_flag
        return desc


    def build_program(in_shape, resample_out, pad_canvas, plan,
                      band_taps=None):
        key = ("single", in_shape, resample_out, pad_canvas, plan,
               band_taps)
        return Handle(jit(make_program_fn(
            resample_out, pad_canvas, plan, band_taps=band_taps,
        )), key)
"""

_BAT_CLEAN = """\
    def build_batched_program(batch_size, in_shape, resample_out,
                              pad_canvas, plan, band_taps=None):
        key = ("batched", batch_size, in_shape, resample_out, pad_canvas,
               plan, band_taps)
        return Handle(jit(vmap(make_program_fn(
            resample_out, pad_canvas, plan, band_taps=band_taps,
        ))), key)


    def submit(image, plan):
        in_shape = bucket_batch(image)
        resample_out = plan.out
        pad_canvas = plan.canvas
        band_taps = select_band_taps(plan)
        key = (in_shape, resample_out, pad_canvas, plan, band_taps)
        return _Group(
            key=key, in_shape=in_shape, resample_out=resample_out,
            pad_canvas=pad_canvas, plan=plan, band_taps=band_taps,
        )


    def _launch(group, batch):
        fn = build_batched_program(
            group.batch_size, group.in_shape, group.resample_out,
            group.pad_canvas, group.plan, group.band_taps,
        )
        return fn(batch)
"""


def _mini(root, ops=_OPS_CLEAN, bat=_BAT_CLEAN):
    _write(root, "flyimg_tpu/ops/mod.py", ops)
    _write(root, "flyimg_tpu/runtime/bat.py", bat)
    return _scan(root)


def test_clean_mini_fixture_passes(tmp_path):
    """The complete fixture — every traced component keyed, grouped, and
    serialized — produces zero program-identity findings."""
    result = _mini(tmp_path)
    assert _rules(result) == set(), [f.format() for f in result.findings]


def test_incomplete_traced_arg_missing_from_key(tmp_path):
    """band_taps feeds the trace but the cache key omits it: two kernel
    variants of one plan would collide on one cache entry."""
    ops = _OPS_CLEAN.replace(
        'key = ("single", in_shape, resample_out, pad_canvas, plan,\n'
        "               band_taps)",
        'key = ("single", in_shape, resample_out, pad_canvas, plan)',
    )
    assert ops != _OPS_CLEAN
    result = _mini(tmp_path, ops=ops)
    msgs = _messages(result, "program-key-incomplete")
    assert any("band_taps" in m for m in msgs), \
        [f.format() for f in result.findings]


def test_incomplete_zeroed_plan_attr(tmp_path):
    """The traced body reads plan.crop while device_plan normalizes crop
    away — the key (which carries the normalized plan) can no longer
    tell crop variants apart."""
    ops = _OPS_CLEAN.replace(
        "return finish(out, plan.quality_flag)",
        "return finish(out, plan.quality_flag, plan.crop)",
    )
    result = _mini(tmp_path, ops=ops)
    msgs = _messages(result, "program-key-incomplete")
    assert any("plan.crop" in m and "normalized away" in m for m in msgs), \
        [f.format() for f in result.findings]


def test_overspecified_untraced_key_field(tmp_path):
    """quality is keyed and passed to the factory but the traced body
    never reads it — pure cache fragmentation."""
    ops = _OPS_CLEAN.replace(
        "def make_program_fn(resample_out, pad_canvas, plan, band_taps=None):",
        "def make_program_fn(resample_out, pad_canvas, plan, band_taps=None,\n"
        "                    quality=None):",
    ).replace(
        'key = ("single", in_shape, resample_out, pad_canvas, plan,\n'
        "               band_taps)",
        'key = ("single", in_shape, resample_out, pad_canvas, plan,\n'
        "               band_taps, quality)",
    ).replace(
        "resample_out, pad_canvas, plan, band_taps=band_taps,\n"
        "        )), key)",
        "resample_out, pad_canvas, plan, band_taps=band_taps,\n"
        "            quality=quality,\n"
        "        )), key)",
    )
    result = _mini(tmp_path, ops=ops)
    msgs = _messages(result, "program-key-overspecified")
    assert any("quality" in m for m in msgs), \
        [f.format() for f in result.findings]


def test_overspecified_unresolvable_key_field(tmp_path):
    """A key element that maps to no factory argument and no shape/batch
    specialization cannot change the compiled program."""
    ops = _OPS_CLEAN.replace(
        'key = ("single", in_shape, resample_out, pad_canvas, plan,\n'
        "               band_taps)",
        'key = ("single", in_shape, resample_out, pad_canvas, plan,\n'
        "               band_taps, encoder_tag)",
    )
    result = _mini(tmp_path, ops=ops)
    msgs = _messages(result, "program-key-overspecified")
    assert any("encoder_tag" in m for m in msgs), \
        [f.format() for f in result.findings]


def test_drift_group_key_omits_component(tmp_path):
    """The submit() group key drops band_taps while the batched program
    cache keys it: requests with different K would share one launch."""
    bat = _BAT_CLEAN.replace(
        "key = (in_shape, resample_out, pad_canvas, plan, band_taps)",
        "key = (in_shape, resample_out, pad_canvas, plan)",
    )
    assert bat != _BAT_CLEAN
    result = _mini(tmp_path, bat=bat)
    msgs = _messages(result, "program-key-drift")
    assert any("group key omits `band_taps`" in m for m in msgs), \
        [f.format() for f in result.findings]


def test_drift_program_key_omits_grouped_component(tmp_path):
    """The reverse direction: the batched program-cache key drops
    band_taps while the group key still carries it."""
    bat = _BAT_CLEAN.replace(
        'key = ("batched", batch_size, in_shape, resample_out, pad_canvas,\n'
        "               plan, band_taps)",
        'key = ("batched", batch_size, in_shape, resample_out, pad_canvas,\n'
        "               plan)",
    )
    assert bat != _BAT_CLEAN
    result = _mini(tmp_path, bat=bat)
    msgs = _messages(result, "program-key-drift")
    assert any("program-cache key omits `band_taps`" in m for m in msgs), \
        [f.format() for f in result.findings]


def test_drift_descriptor_never_reads_component(tmp_path):
    """plan_descriptor stops serializing band_taps: dense and banded
    programs become indistinguishable in /debug/plans."""
    ops = _OPS_CLEAN.replace(
        '        desc["band_taps"] = band_taps\n', ""
    ).replace(
        "def plan_descriptor(plan, *, in_shape=None, resample_out=None,\n"
        "                        pad_canvas=None, band_taps=None):",
        "def plan_descriptor(plan, *, in_shape=None, resample_out=None,\n"
        "                        pad_canvas=None, band_taps=None):\n"
        "        del band_taps",
    )
    # `del` is not a Load, so the parameter counts as never read
    result = _mini(tmp_path, ops=ops)
    msgs = _messages(result, "program-key-drift")
    assert any(
        "never reads keyed program component `band_taps`" in m
        for m in msgs
    ), [f.format() for f in result.findings]


def test_drift_descriptor_misses_plan_attr(tmp_path):
    """The traced body reads plan.sharpen_sigma but the descriptor never
    does — programs differing in it look identical in the ledger."""
    ops = _OPS_CLEAN.replace(
        "return finish(out, plan.quality_flag)",
        "return finish(out, plan.quality_flag, plan.sharpen_sigma)",
    )
    result = _mini(tmp_path, ops=ops)
    msgs = _messages(result, "program-key-drift")
    assert any("plan.sharpen_sigma" in m for m in msgs), \
        [f.format() for f in result.findings]


# ---------------------------------------------------------------------------
# jax-retrace-hazard


_CALLER_RAW = """\

    def run(image, plan):
        h, w = image.shape[0], image.shape[1]
        in_shape = (h, w)
        fn = build_program(in_shape, plan.out, None, plan, None)
        return fn(image)
"""

_CALLER_BUCKETED = """\

    def run(image, plan):
        h, w = image.shape[0], image.shape[1]
        in_shape = (_bucket_dim(h), _bucket_dim(w))
        fn = build_program(in_shape, plan.out, None, plan, None)
        return fn(image)
"""

_CALLER_SUPPRESSED = """\

    def run(image, plan):
        h, w = image.shape[0], image.shape[1]
        # deliberate exact-frame path, see docs/kernels.md
        # flylint: disable=jax-retrace-hazard
        in_shape = (h, w)
        fn = build_program(in_shape, plan.out, None, plan, None)
        return fn(image)
"""


def test_retrace_hazard_unbucketed_shape_trips(tmp_path):
    result = _mini(tmp_path, ops=_OPS_CLEAN + _CALLER_RAW)
    msgs = _messages(result, "jax-retrace-hazard")
    assert any("in_shape" in m and "bucketing helper" in m for m in msgs), \
        [f.format() for f in result.findings]


def test_retrace_hazard_bucketed_shape_passes(tmp_path):
    result = _mini(tmp_path, ops=_OPS_CLEAN + _CALLER_BUCKETED)
    assert "jax-retrace-hazard" not in _rules(result), \
        [f.format() for f in result.findings]


def test_retrace_hazard_inline_suppression(tmp_path):
    """The finding lands on the tainted assignment, so the written
    justification lives next to the deliberate exact-shape choice."""
    result = _mini(tmp_path, ops=_OPS_CLEAN + _CALLER_SUPPRESSED)
    assert "jax-retrace-hazard" not in _rules(result), \
        [f.format() for f in result.findings]
    assert result.suppressed >= 1


# ---------------------------------------------------------------------------
# real-file mutations: the three identity systems in ops/compose.py +
# runtime/batcher.py, each desynchronized one at a time


def _real_sources():
    out = {}
    for relpath in ("flyimg_tpu/ops/compose.py",
                    "flyimg_tpu/runtime/batcher.py"):
        with open(os.path.join(REPO_ROOT, relpath), encoding="utf-8") as fh:
            out[relpath] = fh.read()
    return out


def _scan_real(tmp_path, mutate=None):
    sources = _real_sources()
    if mutate is not None:
        relpath, old, new = mutate
        text = sources[relpath]
        assert old in text, f"mutation anchor drifted: {old!r}"
        sources[relpath] = text.replace(old, new)
    for relpath, text in sources.items():
        path = os.path.join(str(tmp_path), relpath)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w", encoding="utf-8") as fh:
            fh.write(text)
    return _scan(tmp_path)


def test_real_copy_scans_clean(tmp_path):
    """Verbatim compose.py + batcher.py: zero program-identity findings
    (the two deliberate exact-frame branches ride their inline
    suppressions)."""
    result = _scan_real(tmp_path)
    assert _rules(result) == set(), [f.format() for f in result.findings]
    assert result.suppressed >= 2  # the exact-frame jax-retrace-hazards


def test_real_drift_group_key_loses_band_taps(tmp_path):
    """Identity system 1/3, submit() group key: dropping band_taps is
    caught as program-key-drift."""
    result = _scan_real(tmp_path, mutate=(
        "flyimg_tpu/runtime/batcher.py",
        "device_plan, rotate_dynamic, band_taps,",
        "device_plan, rotate_dynamic,",
    ))
    msgs = _messages(result, "program-key-drift")
    assert any("group key omits `band_taps`" in m for m in msgs), \
        [f.format() for f in result.findings]


def test_real_drift_program_key_loses_band_taps(tmp_path):
    """Identity system 2/3, batched program-cache key: dropping
    band_taps is caught as program-key-drift (and as incomplete — the
    trace still reads it)."""
    result = _scan_real(tmp_path, mutate=(
        "flyimg_tpu/runtime/batcher.py",
        "        tuple(mesh.shape.items()) if mesh is not None else None,\n"
        "        band_taps,\n",
        "        tuple(mesh.shape.items()) if mesh is not None else None,\n",
    ))
    rules = _rules(result)
    assert "program-key-drift" in rules, \
        [f.format() for f in result.findings]
    msgs = _messages(result, "program-key-drift")
    assert any("band_taps" in m for m in msgs)
    assert any(
        "band_taps" in m
        for m in _messages(result, "program-key-incomplete")
    )


def test_real_drift_descriptor_loses_band_taps(tmp_path):
    """Identity system 3/3, plan_descriptor: dropping the band_taps
    serialization is caught as program-key-drift."""
    result = _scan_real(tmp_path, mutate=(
        "flyimg_tpu/ops/compose.py",
        '        desc["kernel"] = "banded" if band_taps is not None '
        'else "dense"\n'
        "        if band_taps is not None:\n"
        '            desc["band_taps"] = list(band_taps)\n',
        "",
    ))
    msgs = _messages(result, "program-key-drift")
    assert any(
        "never reads keyed program component `band_taps`" in m
        for m in msgs
    ), [f.format() for f in result.findings]


def test_real_incomplete_single_key_loses_band_taps(tmp_path):
    """The single-image program cache (ops/compose.build_program):
    dropping band_taps from its key is caught as program-key-incomplete
    — the traced body still closes over it."""
    result = _scan_real(tmp_path, mutate=(
        "flyimg_tpu/ops/compose.py",
        '        "single", in_shape, resample_out, pad_canvas, pad_offset,'
        " plan,\n"
        "        band_taps,\n",
        '        "single", in_shape, resample_out, pad_canvas, pad_offset,'
        " plan,\n",
    ))
    msgs = _messages(result, "program-key-incomplete")
    assert any("band_taps" in m for m in msgs), \
        [f.format() for f in result.findings]


# ---------------------------------------------------------------------------
# regression pins for the real findings this PR fixed


def test_descriptor_carries_pad_offset_and_background():
    """program-key-drift fix: plan_descriptor serializes pad_offset and
    the fill background wherever a canvas or rotate paints them — two
    extent programs differing only in offset or fill must never share a
    descriptor."""
    from flyimg_tpu.ops.compose import plan_descriptor
    from flyimg_tpu.spec.options import OptionsBag
    from flyimg_tpu.spec.plan import build_plan

    plan_a = build_plan(OptionsBag("w_100,h_80,r_3,bg_red"), 400, 300)
    plan_b = build_plan(OptionsBag("w_100,h_80,r_3,bg_green"), 400, 300)
    a = plan_descriptor(plan_a.device_plan(), in_shape=(300, 400),
                        resample_out=(60, 100), pad_canvas=(80, 100),
                        pad_offset=(10, 0))
    b = plan_descriptor(plan_b.device_plan(), in_shape=(300, 400),
                        resample_out=(60, 100), pad_canvas=(80, 100),
                        pad_offset=(10, 0))
    assert a["pad_offset"] == [10, 0]
    assert "background" in a and "background" in b
    assert a != b, "descriptors must distinguish fill backgrounds"
    c = plan_descriptor(plan_a.device_plan(), in_shape=(300, 400),
                        resample_out=(60, 100), pad_canvas=(80, 100),
                        pad_offset=(0, 0))
    assert a != c, "descriptors must distinguish pad offsets"


def test_exact_frame_suppressions_are_justified():
    """The two deliberate jax-retrace-hazard suppressions (the static-
    rotate exact-frame branches in run_plan and BatchController.submit)
    each carry their written rationale on the adjacent lines — the
    suppression-with-justification policy of docs/static-analysis.md."""
    for relpath in ("flyimg_tpu/ops/compose.py",
                    "flyimg_tpu/runtime/batcher.py"):
        with open(os.path.join(REPO_ROOT, relpath), encoding="utf-8") as fh:
            text = fh.read()
        idx = text.index("# flylint: disable=jax-retrace-hazard")
        context = text[max(0, idx - 500):idx]
        assert "DELIBERATE" in context.upper(), relpath
        assert "halo" in context, relpath  # the correctness rationale


def test_real_drift_group_key_loses_depth(tmp_path):
    """The sample depth (8 or 16 bits) is a component of all three
    identity systems like band_taps: dropping it from the submit() group
    key, where 8-bit and 16-bit members would then share a launch, is
    caught as program-key-drift."""
    result = _scan_real(tmp_path, mutate=(
        "flyimg_tpu/runtime/batcher.py",
        "device_plan, rotate_dynamic, band_taps, depth,",
        "device_plan, rotate_dynamic, band_taps,",
    ))
    msgs = _messages(result, "program-key-drift")
    assert any("group key omits `depth`" in m for m in msgs), \
        [f.format() for f in result.findings]
