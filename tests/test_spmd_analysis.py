"""SPMD contract passes: sharding, hostsync, pallas (ISSUE 13).

Tier-1 contract, extending tests/test_analysis.py + test_protocols.py's
pattern to the three new pass families:

- the real package gates CLEAN under the sharding/hostsync/pallas passes
  (the shipped baseline stays empty), while the known-bad fixture corpus
  trips SHD001-SHD004, HSY001-HSY003, and PAL001-PAL004;
- the passes detect what they guard, ON THE LIVE TREE: renaming a mesh
  axis in parallel/mesh.py onto an existing one (in memory) trips
  SHD002, flipping a checked shard_map's check_rep (a fixture copy of
  the compat wrapper mesh.py once carried) trips SHD004, wrapping timeshard's
  all_gather in a process_index branch trips HSY001, and deleting a
  ``wait()`` from the explicit-DMA kernel in ops/pallas_scan.py trips
  PAL001 — exactly the pod-hang bug families the multi-host and kernel
  PRs (ROADMAP items 1-2) are about to grow;
- annotations are load-bearing: stripping the sharding-ok waiver off the
  compat shard_map's check_vma forward resurfaces SHD004, and a
  waiver-stripping comment-only edit resurfaces SHD/HSY/PAL findings
  THROUGH the warm/partial cache (the PR-4 stale-cache-soundness
  discipline applied to the new families);
- a pallas-clean DMA kernel (start → compute → wait, wait_send/wait_recv
  pairs) and the canonical lead-host logging idiom stay UNflagged — the
  passes have teeth, not trigger-happiness;
- ANALYZER_VERSION 3 manifests self-invalidate (the version-4 bump means
  a stale on-disk cache can never replay a pre-SPMD finding list), every
  requested pass reports explicit ZEROS on clean runs, and the new
  finding codes round-trip ``--format json`` with stable IDs through a
  warm cache.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

import asyncrl_tpu
from asyncrl_tpu import analysis
from asyncrl_tpu.analysis import cache, core, report

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.dirname(os.path.abspath(asyncrl_tpu.__file__))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "analysis")

MESH = os.path.join(PACKAGE, "parallel", "mesh.py")
# The checked compat shard_map wrapper mesh.py once carried, kept as a
# fixture so its two mutation proofs outlive the wrapper.
COMPAT_SMAP = os.path.join(FIXTURES, "good_compat_shard_map.py")
TIMESHARD = os.path.join(PACKAGE, "parallel", "timeshard.py")
PALLAS_SCAN = os.path.join(PACKAGE, "ops", "pallas_scan.py")

SPMD_PASSES = ("sharding", "hostsync", "pallas")


def codes(findings):
    return {f.code for f in findings}


def _lint(src, passes=SPMD_PASSES):
    return analysis.check_source(textwrap.dedent(src), passes=passes)


def _check_single(path, src, passes):
    project = core.Project([core.SourceModule(path, src)])
    return analysis.run_passes(project, passes)


def _mutated(path, needle, replacement, count=1):
    src = open(path).read()
    assert needle in src, f"needle not found in {path}: {needle!r}"
    mutated = src.replace(needle, replacement, count)
    assert mutated != src
    return mutated


# ----------------------------------------------------------- the package


def test_package_gates_clean_under_spmd_passes():
    findings = analysis.check_paths([PACKAGE], passes=SPMD_PASSES)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_entry_points_gate_clean_under_spmd_passes():
    """The lint.sh entry-point run (scripts/*.py + chip_smoke.py +
    __graft_entry__.py) is clean under the same passes it gates with."""
    paths = [os.path.join(REPO, "scripts")] + [
        os.path.join(REPO, f) for f in ("chip_smoke.py", "__graft_entry__.py")
    ]
    findings = analysis.check_paths(
        paths, passes=("configflow",) + SPMD_PASSES
    )
    assert findings == [], "\n".join(f.render() for f in findings)


# ------------------------------------------------------- fixture corpus


@pytest.mark.parametrize(
    "fixture, expected",
    [
        ("bad_sharding.py", {"SHD001", "SHD002", "SHD003", "SHD004"}),
        ("bad_hostsync.py", {"HSY001", "HSY002", "HSY003"}),
        ("bad_pallas.py", {"PAL001", "PAL002", "PAL003", "PAL004"}),
    ],
)
def test_fixture_corpus_is_flagged(fixture, expected):
    findings = analysis.check_paths([os.path.join(FIXTURES, fixture)])
    assert expected <= codes(findings), (
        f"{fixture} must trip {sorted(expected)}; got "
        + "\n".join(f.render() for f in findings)
    )


# ------------------------------------- deletion proofs on the LIVE tree


def test_renaming_a_mesh_axis_trips_shd002():
    """The acceptance proof: parallel/mesh.py is clean, and the careless
    rename — TIME_AXIS landing on the string DP_AXIS already owns — is
    caught (dp_axes would silently exclude the data-parallel axis and
    every gradient all-reduce would disappear)."""
    src = open(MESH).read()
    assert not _check_single(MESH, src, ("sharding",))
    mutated = _mutated(MESH, 'TIME_AXIS = "sp"', 'TIME_AXIS = "dp"')
    findings = _check_single(MESH, mutated, ("sharding",))
    assert any(
        f.code == "SHD002" and "TIME_AXIS" in f.message for f in findings
    ), "\n".join(f.render() for f in findings)


def test_flipping_check_rep_trips_shd004():
    assert not _check_single(
        COMPAT_SMAP, open(COMPAT_SMAP).read(), ("sharding",)
    )
    # The comma-suffixed needle targets the CODE kwarg, not the docstring
    # above it that quotes "check_rep=True," in prose.
    src = open(COMPAT_SMAP).read()
    head, sep, tail = src.rpartition("check_rep=True,")
    assert sep, "needle not found"
    findings = _check_single(
        COMPAT_SMAP, head + "check_rep=False," + tail, ("sharding",)
    )
    assert any(f.code == "SHD004" for f in findings), (
        "\n".join(f.render() for f in findings)
    )


def test_stripping_the_check_vma_waiver_resurfaces_shd004():
    """The compat shard_map's explicit check_vma=False forward carries
    a sharding-ok waiver; it is load-bearing."""
    src = "\n".join(
        line
        for line in open(COMPAT_SMAP).read().split("\n")
        if "lint: sharding-ok" not in line
    )
    findings = _check_single(COMPAT_SMAP, src, ("sharding",))
    assert any(f.code == "SHD004" for f in findings), (
        "\n".join(f.render() for f in findings)
    )


def test_host_guarding_the_all_gather_trips_hsy001():
    """Wrapping the distributed scan's all_gather in a process_index
    branch (the exact 'only the lead host needs it' refactor a reviewer
    would wave through) is a pod deadlock — HSY001; the file is clean."""
    src = open(TIMESHARD).read()
    assert not _check_single(TIMESHARD, src, ("hostsync",))
    needle = "    a_all = jax.lax.all_gather(a_seg, axis_name)"
    mutated = _mutated(
        TIMESHARD,
        needle,
        "    if jax.process_index() == 0:\n"
        "        a_all = jax.lax.all_gather(a_seg, axis_name)",
    )
    findings = _check_single(TIMESHARD, mutated, ("hostsync",))
    assert any(f.code == "HSY001" for f in findings), (
        "\n".join(f.render() for f in findings)
    )


def test_deleting_a_dma_wait_trips_pal001():
    """Deleting the write-back DMA's wait() from the explicit-DMA kernel
    leaves the copy in flight at kernel exit — PAL001; the real file is
    clean. (The runtime symptom would be torn output or a hung chip —
    the lint-time symptom is this test.)"""
    src = open(PALLAS_SCAN).read()
    assert not _check_single(PALLAS_SCAN, src, ("pallas",))
    mutated = "\n".join(
        line for line in src.split("\n")
        if line.strip() != "copy_out.wait()"
    )
    assert mutated != src
    findings = _check_single(PALLAS_SCAN, mutated, ("pallas",))
    assert any(f.code == "PAL001" for f in findings), (
        "\n".join(f.render() for f in findings)
    )


# --------------------------------------------------- pass semantics


def test_clean_dma_kernel_and_rdma_pairs_are_not_flagged():
    """start → compute → wait is the discipline, not a finding; the
    send/recv split waits of a remote copy pair up too. Kernels cannot
    raise at runtime, so the exception edges that make host-side lease
    leaks reportable stay silent here."""
    findings = _lint(
        """
        import jax
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def kernel(x_hbm, o_hbm, scratch, sems):
            cp = pltpu.make_async_copy(x_hbm, scratch, sems.at[0])
            cp.start()
            compute(scratch)
            cp.wait()
            o_hbm[...] = scratch[...]

        def ring_step(buf, nbr, send_sem, recv_sem):
            op = pltpu.make_async_remote_copy(
                buf, nbr, send_sem=send_sem, recv_sem=recv_sem,
                device_id=1,
            )
            op.start()
            op.wait_send()
            op.wait_recv()
        """
    )
    assert findings == [], "\n".join(f.render() for f in findings)


def test_cross_module_axis_collision_trips_shd002_symmetrically():
    """The alias map is project-wide AND symmetric: a NEW module
    re-declaring another module's axis string (the cross-file careless
    rename) flags at BOTH declarations — which one is 'the new one' is
    unknowable statically, and path sort order must not decide blame."""
    a = core.SourceModule("a_axes.py", 'DP_AXIS = "dp"\n')
    b = core.SourceModule("b_axes.py", 'MODEL_AXIS = "dp"\n')
    findings = analysis.run_passes(core.Project([a, b]), ("sharding",))
    assert {f.path for f in findings if f.code == "SHD002"} == {
        "a_axes.py", "b_axes.py"
    }, "\n".join(f.render() for f in findings)


def test_shadowed_same_named_method_is_still_walked():
    """Function enumeration must not collapse on name: a host-divergent
    collective in A.step is found even when a later class B defines its
    own step (same-named methods recur across classes in every module
    here — a last-definition-wins index would silently skip A's)."""
    findings = _lint(
        """
        import jax

        class A:
            def step(self, x):
                if jax.process_index() == 0:
                    x = jax.lax.psum(x, "dp")
                return x

        class B:
            def step(self, x):
                return x
        """,
        passes=("hostsync",),
    )
    assert [f.code for f in findings] == ["HSY001"], (
        "\n".join(f.render() for f in findings)
    )


def test_attribute_store_of_rank_does_not_taint_the_object():
    """``self.rank = process_index()`` taints nothing but the value: a
    later ``if self.debug:`` branch is not host-divergent."""
    findings = _lint(
        """
        import jax

        class T:
            def setup(self, x):
                self.rank = jax.process_index()
                if self.debug:
                    x = jax.lax.psum(x, "dp")
                return x
        """,
        passes=("hostsync",),
    )
    assert findings == [], "\n".join(f.render() for f in findings)


def test_semaphore_pairing_is_per_function():
    """Same-named ``sems`` parameters in unrelated kernels must not
    pair up across functions and mask two genuinely unpaired sites."""
    findings = _lint(
        """
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def k1(o_ref, sems):
            pl.semaphore_signal(sems.at[0])

        def k2(o_ref, sems):
            pl.semaphore_wait(sems.at[0])
        """,
        passes=("pallas",),
    )
    assert [f.code for f in findings] == ["PAL001", "PAL001"], (
        "\n".join(f.render() for f in findings)
    )


def test_recv_first_wait_order_is_legal_and_repeats_still_report():
    """The send/recv semaphores are independent — waiting recv before
    send is a legal kernel and must not read as out-of-order, while
    repeating EITHER half-wait is still PAL002."""
    assert not _lint(
        """
        from jax.experimental.pallas import tpu as pltpu

        def ring_step(buf, nbr, send_sem, recv_sem):
            op = pltpu.make_async_remote_copy(
                buf, nbr, send_sem=send_sem, recv_sem=recv_sem,
                device_id=1,
            )
            op.start()
            op.wait_recv()
            op.wait_send()
        """
    )
    doubled = _lint(
        """
        from jax.experimental.pallas import tpu as pltpu

        def ring_step(buf, nbr, send_sem, recv_sem):
            op = pltpu.make_async_remote_copy(
                buf, nbr, send_sem=send_sem, recv_sem=recv_sem,
                device_id=1,
            )
            op.start()
            op.wait_send()
            op.wait_send()
            op.wait_recv()
        """
    )
    assert "PAL002" in codes(doubled), (
        "\n".join(f.render() for f in doubled)
    )


def test_query_in_early_returning_branch_is_not_before_initialize():
    """The canonical local-mode escape hatch — a single-host branch that
    builds its mesh and RETURNS — is mutually exclusive with the
    initialize call after it; only fall-through queries flag."""
    findings = _lint(
        """
        import jax
        from asyncrl_tpu.parallel.mesh import make_mesh

        def launch(local):
            if local:
                return make_mesh((-1,), ("dp",))
            jax.distributed.initialize()
            return make_mesh((-1,), ("dp",))
        """,
        passes=("hostsync",),
    )
    assert findings == [], "\n".join(f.render() for f in findings)
    straight = _lint(
        """
        import jax

        def launch():
            devs = jax.devices()
            jax.distributed.initialize()
            return devs
        """,
        passes=("hostsync",),
    )
    assert [f.code for f in straight] == ["HSY002"]


def test_module_level_host_divergence_is_walked_too():
    """A launch SCRIPT that barriers only on the lead host at module
    scope hangs the pod exactly like a function body would — the
    entry-point lint gate must see it."""
    findings = _lint(
        """
        import jax
        from jax.experimental import multihost_utils

        jax.distributed.initialize()
        if jax.process_index() == 0:
            multihost_utils.sync_global_devices("ckpt")
        """,
        passes=("hostsync",),
    )
    assert [f.code for f in findings] == ["HSY003"], (
        "\n".join(f.render() for f in findings)
    )


def test_positional_out_shape_is_recognized():
    """jax allows out_shape as the second positional argument; missing
    it misclassified the output ref as an input (PAL004 on a correct
    kernel) and silently skipped PAL003."""
    assert not _lint(
        """
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def k(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2

        out = pl.pallas_call(k, jax.ShapeDtypeStruct((8, 128), jnp.float32))
        """,
        passes=("pallas",),
    )
    ragged = _lint(
        """
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def k(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        out = pl.pallas_call(
            k, jax.ShapeDtypeStruct((8, 256), jnp.float32),
            grid=(2,),
            out_specs=pl.BlockSpec((8, 100), lambda i: (0, i)),
        )
        """,
        passes=("pallas",),
    )
    assert "PAL003" in codes(ragged), (
        "\n".join(f.render() for f in ragged)
    )


def test_match_on_process_index_diverges_every_case():
    """``match jax.process_index():`` is the same divergence as the if
    form — every case body runs on a subset of hosts."""
    findings = _lint(
        """
        import jax
        from jax.experimental import multihost_utils

        def go():
            match jax.process_index():
                case 0:
                    multihost_utils.sync_global_devices("ckpt")
                case _:
                    pass
        """,
        passes=("hostsync",),
    )
    assert [f.code for f in findings] == ["HSY003"]


def test_positional_only_kernel_params_keep_ref_classification():
    """``def k(a_ref, /, o_ref)``: posonly params are inputs too — the
    undeclared in-place store into a_ref must still report."""
    findings = _lint(
        """
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def k(a_ref, /, o_ref):
            a_ref[0] = 1.0
            o_ref[0] = a_ref[0]

        out = pl.pallas_call(k, jax.ShapeDtypeStruct((8,), jnp.float32))
        """,
        passes=("pallas",),
    )
    assert [f.code for f in findings] == ["PAL004"]


def test_lead_host_logging_is_not_flagged():
    """``if process_index() == 0: print(...)`` is the canonical idiom —
    only collective-reaching code in the divergent region reports."""
    findings = _lint(
        """
        import jax

        def report(metrics):
            if jax.process_index() == 0:
                print(metrics)

        def fine():
            jax.distributed.initialize()
            return jax.devices()
        """
    )
    assert findings == [], "\n".join(f.render() for f in findings)


def test_single_p_spec_is_a_valid_prefix_not_an_arity_finding():
    """in_specs=P() (a pytree prefix of the whole argument tuple) and
    runtime spec tuples must not trip SHD001; a rebindable Name target
    is skipped rather than resolved to the wrong def."""
    findings = _lint(
        """
        import jax
        from jax.sharding import PartitionSpec as P
        from asyncrl_tpu.parallel.mesh import make_mesh, shard_map

        mesh = make_mesh((-1,), ("dp",))

        def body(x, y):
            return x

        step = shard_map(body, mesh=mesh, in_specs=P(), out_specs=P())

        def build(wrapped_fn):
            wrapped = wrapped_fn  # rebound local shadows any def
            return shard_map(
                wrapped, mesh=mesh, in_specs=(P(),), out_specs=P()
            )
        """,
        passes=("sharding",),
    )
    assert findings == [], "\n".join(f.render() for f in findings)


def test_defaulted_params_widen_the_legal_spec_arity():
    """in_specs may cover only the non-default args — any arity in
    [n_params - n_defaults, n_params] is a legal call; below it still
    flags, and a sharding-ok waiver silences SHD001 like its siblings."""
    base = """
    from jax.sharding import PartitionSpec as P
    from asyncrl_tpu.parallel.mesh import make_mesh, shard_map

    mesh = make_mesh((-1,), ("dp",))

    def body(a, b, c=None):
        return a

    step = shard_map(body, mesh=mesh, in_specs={specs}, out_specs=P())
    """
    assert not _lint(base.format(specs="(P(), P())"), passes=("sharding",))
    assert not _lint(
        base.format(specs="(P(), P(), P())"), passes=("sharding",)
    )
    short = _lint(base.format(specs="(P(),)"), passes=("sharding",))
    assert [f.code for f in short] == ["SHD001"]
    waived = _lint(
        base.replace(
            "    step = shard_map(",
            "    # lint: sharding-ok(fixture: specs for a vmapped variant)"
            "\n    step = shard_map(",
        ).format(specs="(P(),)"),
        passes=("sharding",),
    )
    assert waived == [], "\n".join(f.render() for f in waived)


def test_factory_param_shadowing_a_def_is_not_shd001():
    """The wrap-a-passed-in-function factory (the most common shard_map
    idiom) must not resolve the parameter name to a same-named module
    def and compare against the wrong signature."""
    findings = _lint(
        """
        from jax.sharding import PartitionSpec as P
        from asyncrl_tpu.parallel.mesh import make_mesh, shard_map

        mesh = make_mesh((-1,), ("dp",))

        def body(a, b):
            return a

        def build(body):
            return shard_map(
                body, mesh=mesh, in_specs=(P(),), out_specs=P()
            )
        """,
        passes=("sharding",),
    )
    assert findings == [], "\n".join(f.render() for f in findings)


def test_variable_scratch_shapes_skips_pal004_not_misclassifies():
    """A non-literal scratch_shapes makes the kernel's parameter layout
    unknowable: the check must skip, not count zero scratch refs and
    flag a correct output store."""
    findings = _lint(
        """
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def k(x_ref, o_ref, s_ref):
            o_ref[0] = x_ref[0]

        def build(scratch):
            return pl.pallas_call(
                k,
                out_shape=jax.ShapeDtypeStruct((8,), jnp.float32),
                scratch_shapes=scratch,
            )
        """,
        passes=("pallas",),
    )
    assert findings == [], "\n".join(f.render() for f in findings)


def test_pallas_module_gate_keys_on_resolved_import():
    """Only true jax.experimental.pallas importers join the analyzed
    set — a module importing a pallas-NAMED wrapper (ops.pallas_scan's
    public functions) must not re-arm the generic start/wait tracking."""
    from asyncrl_tpu.analysis import pallas as pallas_pass

    project = analysis.load_paths([PACKAGE])
    paths = {m.path for m in pallas_pass._pallas_modules(project)}
    assert any(p.endswith("ops/pallas_scan.py") for p in paths)
    assert not any(p.endswith("ops/scan.py") for p in paths), (
        "ops/scan.py imports only pallas-named wrappers, not pallas"
    )


def test_multi_output_kernel_with_runtime_dims_is_not_pal004():
    """Output count comes from the out_shape AST structure: a two-struct
    tuple with runtime shapes is two outputs, and a store into the first
    output ref must not read as an input-ref store."""
    findings = _lint(
        """
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def k(x_ref, o1_ref, o2_ref):
            o1_ref[0] = x_ref[0]
            o2_ref[0] = x_ref[0]

        def build(shape):
            return pl.pallas_call(
                k,
                out_shape=(jax.ShapeDtypeStruct(shape, jnp.float32),
                           jax.ShapeDtypeStruct(shape, jnp.float32)),
            )
        """,
        passes=("pallas",),
    )
    assert findings == [], "\n".join(f.render() for f in findings)


def test_spmd_waivers_are_honored():
    """Each family's waiver silences exactly its declared line."""
    findings = _lint(
        """
        import jax
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        from jax.sharding import PartitionSpec as P
        from asyncrl_tpu.parallel.mesh import make_mesh, shard_map

        mesh = make_mesh((-1,), ("dp",))

        def body(x):
            return x

        solo = shard_map(
            body, mesh=mesh, in_specs=(P(),), out_specs=P(),
            check_rep=False,
        )  # lint above covers nothing: the call line carries the waiver

        def sync(x):
            if jax.process_index() == 0:
                # lint: hostsync-ok(fixture: congruence argued in test)
                x = jax.lax.psum(x, "dp")
            return x

        def fire_and_forget(x_hbm, scratch, sems):
            # lint: pallas-ok(fixture: waited by the next grid step)
            cp = pltpu.make_async_copy(x_hbm, scratch, sems.at[0])
            cp.start()
        """
    )
    # Only the unwaived check_rep=False remains.
    assert [f.code for f in findings] == ["SHD004"], (
        "\n".join(f.render() for f in findings)
    )
    waived = _lint(
        """
        from jax.sharding import PartitionSpec as P
        from asyncrl_tpu.parallel.mesh import make_mesh, shard_map

        mesh = make_mesh((-1,), ("dp",))

        def body(x):
            return x

        # lint: sharding-ok(fixture: replication proven by identity test)
        solo = shard_map(
            body, mesh=mesh, in_specs=(P(),), out_specs=P(),
            check_rep=False,
        )
        """,
        passes=("sharding",),
    )
    assert waived == [], "\n".join(f.render() for f in waived)


# ------------------------------------------------- cache & report seams


def _waived_tree(tmp_path):
    (tmp_path / "kern.py").write_text(
        textwrap.dedent(
            """
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu

            def fire(x_hbm, scratch, sems):
                # lint: pallas-ok(fixture: next grid step waits)
                cp = pltpu.make_async_copy(x_hbm, scratch, sems.at[0])
                cp.start()
            """
        )
    )
    (tmp_path / "spmd.py").write_text(
        textwrap.dedent(
            """
            import jax
            from jax.sharding import PartitionSpec as P
            from asyncrl_tpu.parallel.mesh import make_mesh, shard_map

            mesh = make_mesh((-1,), ("dp",))

            def body(x):
                return x

            # lint: sharding-ok(fixture: replication proven elsewhere)
            step = shard_map(
                body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                check_rep=False,
            )

            def sync(x):
                if jax.process_index() == 0:
                    # lint: hostsync-ok(fixture: congruent by test)
                    x = jax.lax.psum(x, "dp")
                return x
            """
        )
    )
    (tmp_path / "other.py").write_text("def helper(x):\n    return x\n")


@pytest.mark.parametrize(
    "victim, strip, code",
    [
        ("spmd.py", "sharding-ok", "SHD004"),
        ("spmd.py", "hostsync-ok", "HSY001"),
        ("kern.py", "pallas-ok", "PAL001"),
    ],
)
def test_spmd_waiver_strip_resurfaces_through_the_cache(
    tmp_path, victim, strip, code
):
    """The PR-4 stale-cache discipline applied to SHD/HSY/PAL: a
    waiver-stripping comment-only edit must resurface the finding on the
    very next cached (partial) run — a stale cache can never hide it."""
    tree, cache_dir = tmp_path / "src", tmp_path / "cache"
    tree.mkdir()
    _waived_tree(tree)
    cold = analysis.run_analysis([str(tree)], cache_dir=str(cache_dir))
    assert cold.findings == [], [f.render() for f in cold.findings]
    src = (tree / victim).read_text()
    (tree / victim).write_text(
        "\n".join(l for l in src.split("\n") if strip not in l)
    )
    after = analysis.run_analysis([str(tree)], cache_dir=str(cache_dir))
    assert after.stats["cache"] == "partial"
    assert any(f.code == code for f in after.findings), (
        f"{code} hidden by the cache: "
        + "\n".join(f.render() for f in after.findings)
    )


def test_spmd_findings_replay_through_a_warm_manifest(tmp_path):
    tree, cache_dir = tmp_path / "src", tmp_path / "cache"
    tree.mkdir()
    for fixture in ("bad_sharding.py", "bad_hostsync.py", "bad_pallas.py"):
        (tree / fixture).write_text(
            open(os.path.join(FIXTURES, fixture)).read()
        )
    cold = analysis.run_analysis([str(tree)], cache_dir=str(cache_dir))
    warm = analysis.run_analysis([str(tree)], cache_dir=str(cache_dir))
    assert warm.stats["cache"] == "warm"
    assert {
        "SHD001", "SHD002", "SHD003", "SHD004",
        "HSY001", "HSY002", "HSY003",
        "PAL001", "PAL002", "PAL003", "PAL004",
    } <= codes(warm.findings)
    assert [f.render() for f in warm.findings] == [
        f.render() for f in cold.findings
    ]


def test_analyzer_version_bump_invalidates_old_manifests(tmp_path):
    """A version-3 (pre-SPMD) manifest must plan COLD — replaying its
    finding list would silently skip the three new passes."""
    tree, cache_dir = tmp_path / "src", tmp_path / "cache"
    tree.mkdir()
    (tree / "a.py").write_text("X = 1\n")
    analysis.run_analysis([str(tree)], cache_dir=str(cache_dir))
    manifest_path = os.path.join(str(cache_dir), "manifest.json")
    doc = json.load(open(manifest_path))
    # The literal current version is pinned where it is bumped
    # (test_analysis.py's pre-wire-budget test); here only the
    # invariant matters: an older manifest can never replay.
    assert doc["version"] == cache.ANALYZER_VERSION
    doc["version"] = "3"
    json.dump(doc, open(manifest_path, "w"))
    files = core.discover_files([str(tree)])
    hashes = {f: cache.file_sha(f) for f in files}
    plan, _ = cache.plan(
        str(cache_dir), files, hashes, tuple(analysis.PASSES)
    )
    assert plan.mode == "cold"


def test_stats_zeros_name_the_three_new_passes(tmp_path):
    (tmp_path / "clean.py").write_text("def f(x):\n    return x\n")
    result = analysis.run_analysis([str(tmp_path)])
    for name in SPMD_PASSES:
        assert result.stats["findings_per_pass"][name] == 0


def test_spmd_codes_round_trip_json_with_stable_ids_through_warm_cache(
    tmp_path,
):
    """The acceptance bound: ``--format json`` round-trips SHD/HSY/PAL
    findings with stable IDs through a warm cache (the lint_report.json
    schema the CI gate and obs doctor consume)."""
    fixture = os.path.join(FIXTURES, "bad_sharding.py")
    cache_dir = str(tmp_path / "cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    docs = []
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, "-m", "asyncrl_tpu.analysis", fixture,
             "--cache-dir", cache_dir, "--format", "json"],
            capture_output=True, text=True, env=env, timeout=300,
        )
        assert proc.returncode == 1  # the fixture gates
        docs.append(json.loads(proc.stdout))
    cold, warm = docs
    assert cold["stats"]["cache"] == "cold"
    assert warm["stats"]["cache"] == "warm"
    assert warm["findings"] == cold["findings"]
    found = {f["code"] for f in warm["findings"]}
    assert {"SHD001", "SHD002", "SHD003", "SHD004"} <= found
    assert all(
        set(f) >= {"id", "code", "path", "line", "message", "baselined"}
        for f in warm["findings"]
    )
    ids = [f["id"] for f in warm["findings"]]
    assert len(ids) == len(set(ids))
    assert warm["stats"]["findings_per_pass"]["sharding"] >= 4


def test_spmd_ids_are_stable_across_independent_runs():
    for fixture in ("bad_sharding.py", "bad_hostsync.py", "bad_pallas.py"):
        path = os.path.join(FIXTURES, fixture)
        first = analysis.check_paths([path], passes=SPMD_PASSES)
        second = analysis.check_paths([path], passes=SPMD_PASSES)
        assert first, f"{fixture} must produce findings"
        assert report.finding_ids(first) == report.finding_ids(second)


def test_unknown_spmd_waiver_reason_rules_still_hold():
    """The new tags obey the grammar: a reasonless waiver is ANN004, a
    misspelled tag is ANN005 — never a silent no-op."""
    assert "ANN004" in codes(_lint(
        """
        def f():
            return 1  # lint: hostsync-ok()
        """
    ))
    assert "ANN005" in codes(_lint(
        """
        def f():
            return 1  # lint: shardin-ok(typo)
        """
    ))
