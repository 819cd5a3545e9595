"""shardlint: positive/negative fixtures per rule + the self-lint gate.

Each rule gets at least one snippet that MUST fire and one that MUST NOT
— the negative sides pin down the escape hatches the codebase relies on
(axis constants, ``__layout_deps__``, ``constrain``, suppression
comments). ``test_self_lint`` is the CI gate itself: the tree must stay
clean (or explicitly baselined) under its own analyzer.
"""

import os
import subprocess
import sys
import textwrap

from neuronx_distributed_llama3_2_tpu.analysis import (
    AxisEnv,
    RULES,
    lint_source,
    load_axis_env,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint(src, rule=None):
    findings = lint_source(textwrap.dedent(src), path="fixture.py")
    if rule is not None:
        findings = [f for f in findings if f.rule == rule]
    return findings


# ---------------------------------------------------------------- SL001


def test_sl001_literal_axis_fires():
    fs = _lint(
        """
        import jax

        def f(x):
            return jax.lax.psum(x, "tp")
        """,
        "SL001",
    )
    assert len(fs) == 1
    assert "'tp'" in fs[0].message
    assert "not a MESH_AXES member" not in fs[0].message


def test_sl001_unknown_axis_notes_typo():
    fs = _lint(
        """
        from jax import lax

        def f(x):
            return lax.all_gather(x, "tensor")
        """,
        "SL001",
    )
    assert len(fs) == 1
    assert "not a MESH_AXES member" in fs[0].message


def test_sl001_kwarg_and_wrapper_forms_fire():
    fs = _lint(
        """
        import jax
        from neuronx_distributed_llama3_2_tpu.parallel import mappings

        def f(x):
            a = jax.lax.ppermute(x, axis_name="dp", perm=[(0, 1)])
            b = mappings._all_gather(x, "cp")
            return a, b
        """,
        "SL001",
    )
    assert len(fs) == 2


def test_sl001_constant_or_parameter_ok():
    fs = _lint(
        """
        import jax
        from neuronx_distributed_llama3_2_tpu.parallel.state import TP_AXIS

        def f(x, axis):
            a = jax.lax.psum(x, TP_AXIS)
            b = jax.lax.psum(x, axis)
            return a, b
        """,
        "SL001",
    )
    assert fs == []


def test_sl001_suppression_comment():
    fs = _lint(
        """
        import jax

        def f(x):
            return jax.lax.psum(x, "tp")  # shardlint: disable=SL001
        """,
        "SL001",
    )
    assert fs == []


# ---------------------------------------------------------------- SL002


_SL002_POS = """
    import dataclasses
    from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state

    @dataclasses.dataclass(frozen=True)
    class Block:
        width: int

        def __call__(self, x):
            if parallel_state.sequence_parallel_enabled():
                return x * 2
            return x
"""


def test_sl002_undeclared_layout_reader_fires():
    fs = _lint(_SL002_POS, "SL002")
    assert len(fs) == 1
    assert "sequence_parallel_enabled" in fs[0].message
    assert "__layout_deps__" in fs[0].hint


def test_sl002_layout_deps_declaration_clears():
    fs = _lint(
        _SL002_POS.replace(
            "width: int",
            'width: int\n'
            '        __layout_deps__ = ("sequence_parallel_enabled",)',
        ),
        "SL002",
    )
    assert fs == []


def test_sl002_eq_false_dataclass_ok():
    # eq=False classes hash by identity — no stale-cache-key hazard
    fs = _lint(
        _SL002_POS.replace(
            "@dataclasses.dataclass(frozen=True)",
            "@dataclasses.dataclass(frozen=True, eq=False)",
        ),
        "SL002",
    )
    assert fs == []


def test_sl002_plain_class_ok():
    fs = _lint(
        """
        from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state

        class Block:
            def __call__(self, x):
                return x * parallel_state.get_tensor_model_parallel_size()
        """,
        "SL002",
    )
    assert fs == []


# ---------------------------------------------------------------- SL003


def test_sl003_spec_arity_exceeds_rank_fires():
    fs = _lint(
        """
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from neuronx_distributed_llama3_2_tpu.parallel.layers import constrain

        def f():
            x = jnp.zeros((4, 8))
            return constrain(x, P(None, "tp", None))
        """,
        "SL003",
    )
    assert len(fs) == 1
    assert "3 entries" in fs[0].message and "rank 2" in fs[0].message


def test_sl003_matching_or_shorter_spec_ok():
    fs = _lint(
        """
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def f(y):
            x = jnp.zeros((4, 8, 2))
            a = lax.with_sharding_constraint(x, P(None, "tp"))
            b = lax.with_sharding_constraint(y, P(None, None, None, None))
            x = y  # reassignment: rank no longer known
            c = lax.with_sharding_constraint(x, P(None, "tp", None, None))
            return a, b, c
        """,
        "SL003",
    )
    assert fs == []


def test_sl003_reshape_rank_inference():
    fs = _lint(
        """
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def f(y):
            x = y.reshape(4, 8)
            return lax.with_sharding_constraint(x, P("dp", None, "tp"))
        """,
        "SL003",
    )
    assert len(fs) == 1


# ---------------------------------------------------------------- SL004


def test_sl004_host_effects_in_jit_fire():
    fs = _lint(
        """
        import time
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            t = time.time()
            y = np.asarray(x)
            print(t)
            x.block_until_ready()
            return x
        """,
        "SL004",
    )
    assert len(fs) == 4
    assert any(".block_until_ready()" in f.message for f in fs)


def test_sl004_traced_callee_of_scan_and_shard_map():
    fs = _lint(
        """
        import jax
        from jax import lax

        def body(c, x):
            print(x)
            return c, x

        def g(x):
            import time
            time.time()
            return x

        def run(mesh, xs):
            lax.scan(body, 0, xs)
            jax.shard_map(g, mesh=mesh, in_specs=None, out_specs=None)(xs)
        """,
        "SL004",
    )
    assert len(fs) == 2


def test_sl004_host_calls_outside_traces_ok():
    fs = _lint(
        """
        import time

        def setup(x):
            t = time.time()
            print(t)
            return x
        """,
        "SL004",
    )
    assert fs == []


# ---------------------------------------------------------------- SL005


def test_sl005_raw_constraint_in_shard_map_fires():
    fs = _lint(
        """
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def body(x):
            return lax.with_sharding_constraint(x, P("tp"))

        def run(mesh, x):
            return jax.shard_map(
                body, mesh=mesh, in_specs=P("tp"), out_specs=P("tp")
            )(x)
        """,
        "SL005",
    )
    assert len(fs) == 1
    assert "constrain" in fs[0].hint


def test_sl005_blessed_constrain_ok():
    fs = _lint(
        """
        from jax.sharding import PartitionSpec as P
        from neuronx_distributed_llama3_2_tpu.parallel.layers import constrain

        def body(x):
            return constrain(x, P("tp"))

        def run(mesh, x):
            return jax.shard_map(
                body, mesh=mesh, in_specs=P("tp"), out_specs=P("tp")
            )(x)
        """,
        "SL005",
    )
    assert fs == []


def test_sl005_constraint_outside_shard_map_ok():
    fs = _lint(
        """
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def f(x):
            return lax.with_sharding_constraint(x, P("tp"))
        """,
        "SL005",
    )
    assert fs == []


# ---------------------------------------------------------------- SL006


def test_sl006_unbound_axis_fires():
    fs = _lint(
        """
        from jax import lax

        def body(x):
            return x + lax.axis_index("dp")

        def run(mesh, x):
            return jax.shard_map(
                body, mesh=mesh, in_specs=None, out_specs=None,
                axis_names={"tp"},
            )(x)
        """,
        "SL006",
    )
    assert len(fs) == 1
    assert "'dp'" in fs[0].message and "['tp']" in fs[0].message


def test_sl006_bound_axis_and_unknown_axis_names_ok():
    fs = _lint(
        """
        from jax import lax

        def body(x):
            return x + lax.axis_index("tp")

        def dyn(x):
            return x + lax.axis_index("dp")

        def run(mesh, x, names):
            a = jax.shard_map(
                body, mesh=mesh, in_specs=None, out_specs=None,
                axis_names={"tp"},
            )(x)
            # axis_names not statically resolvable: rule must stay quiet
            b = jax.shard_map(
                dyn, mesh=mesh, in_specs=None, out_specs=None, axis_names=names
            )(x)
            return a, b
        """,
        "SL006",
    )
    assert fs == []


# ---------------------------------------------------------------- SL007


def test_sl007_adhoc_donated_jit_in_serving_fires():
    src = """
    import jax

    class Engine:
        def __init__(self):
            self._fn = jax.jit(lambda c: c, donate_argnums=(0,))
    """
    fs = lint_source(
        textwrap.dedent(src),
        path="neuronx_distributed_llama3_2_tpu/serving/engine.py",
    )
    fs = [f for f in fs if f.rule == "SL007"]
    assert len(fs) == 1
    assert "_register_program" in fs[0].message + fs[0].hint


def test_sl007_registry_helper_and_other_layers_quiet():
    src = """
    import jax

    class Engine:
        def _register_program(self, key_, fn, donate_argnums=()):
            rec = jax.jit(fn, donate_argnums=donate_argnums)
            self._programs[key_] = rec
            return rec

        def _plain(self, fn):
            return jax.jit(fn)  # undonated: not a registry concern
    """
    fs = lint_source(
        textwrap.dedent(src),
        path="neuronx_distributed_llama3_2_tpu/serving/engine.py",
    )
    assert [f for f in fs if f.rule == "SL007"] == []
    # same donated jit OUTSIDE serving/: a different layer's business
    outside = """
    import jax

    many = jax.jit(lambda c: c, donate_argnums=(0,))
    """
    fs = lint_source(
        textwrap.dedent(outside),
        path="neuronx_distributed_llama3_2_tpu/inference/runner.py",
    )
    assert [f for f in fs if f.rule == "SL007"] == []


def test_sl007_donate_argnames_spelling_fires():
    src = """
    from jax import jit

    step = jit(lambda c: c, donate_argnames=("cache",))
    """
    fs = lint_source(
        textwrap.dedent(src),
        path="neuronx_distributed_llama3_2_tpu/serving/scheduler.py",
    )
    assert [f.rule for f in fs if f.rule == "SL007"] == ["SL007"]


# ---------------------------------------------------------------- SL008


_SERVING = "neuronx_distributed_llama3_2_tpu/serving/engine.py"


def test_sl008_mirror_write_outside_funnel_fires():
    src = """
    class Engine:
        def _my_new_path(self, lane):
            self._positions[lane] += 1  # poking the frontier mirror
    """
    fs = lint_source(textwrap.dedent(src), path=_SERVING)
    fs = [f for f in fs if f.rule == "SL008"]
    assert len(fs) == 1
    assert "_positions" in fs[0].message


def test_sl008_resident_and_tuple_targets_fire():
    src = """
    class Engine:
        def refresh(self, x):
            self._d_tokens = x            # resident outside a funnel

        def unpack(self, a, b):
            self._tokens, other = a, b    # tuple-target mirror write
    """
    fs = [f for f in lint_source(textwrap.dedent(src), path=_SERVING)
          if f.rule == "SL008"]
    assert len(fs) == 2


def test_sl008_blessed_funnels_and_other_layers_quiet():
    src = """
    class Engine:
        def _read_and_apply(self, lane):
            self._positions[lane] -= 1    # mirror funnel

        def _flush_state(self, x):
            self._d_tokens = x            # resident funnel

        def _my_new_path(self):
            self._scratch = 0             # unprotected attr: fine
    """
    fs = lint_source(textwrap.dedent(src), path=_SERVING)
    assert [f for f in fs if f.rule == "SL008"] == []
    # the same rogue write OUTSIDE serving/ is not SL008's business
    outside = """
    class Thing:
        def poke(self, lane):
            self._positions[lane] = 0
    """
    fs = lint_source(
        textwrap.dedent(outside),
        path="neuronx_distributed_llama3_2_tpu/inference/runner.py",
    )
    assert [f for f in fs if f.rule == "SL008"] == []


def test_sl008_line_suppression():
    src = """
    class Engine:
        def _my_new_path(self, lane):
            self._positions[lane] = 0  # shardlint: disable=SL008
    """
    fs = lint_source(textwrap.dedent(src), path=_SERVING)
    assert [f for f in fs if f.rule == "SL008"] == []


# ----------------------------------------------------------- machinery


def test_fingerprint_survives_line_moves():
    src = """
    import jax

    def f(x):
        return jax.lax.psum(x, "tp")
    """
    a = _lint(src, "SL001")[0]
    b = _lint("\n\n# a comment\n" + textwrap.dedent(src), "SL001")[0]
    assert a.line != b.line
    assert a.fingerprint == b.fingerprint


def test_skip_file_comment():
    fs = _lint(
        """
        # shardlint: skip-file
        import jax

        def f(x):
            return jax.lax.psum(x, "tp")
        """
    )
    assert fs == []


def test_load_axis_env_matches_state_py():
    env = load_axis_env(REPO_ROOT)
    assert env.axes == frozenset({"pp", "dp", "cp", "ep", "tp"})
    assert env.constants["TP_AXIS"] == "tp"
    assert AxisEnv.default().axes == env.axes


def test_rule_catalogue_complete():
    assert sorted(RULES) == [
        "SL001", "SL002", "SL003", "SL004", "SL005", "SL006", "SL007",
        "SL008",
    ]


# ------------------------------------------------------------ the gate


def test_self_lint():
    """The tier-1 CI gate: the repo's own sources must be shardlint-clean
    (modulo the reviewed baseline). Runs the real CLI so the exit-status
    contract is what's tested."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts", "shardlint_gate.py"), "--self"],
        capture_output=True,
        text=True,
        cwd=REPO_ROOT,
        timeout=300,
    )
    assert proc.returncode == 0, (
        "shardlint gate failed:\n" + proc.stdout + proc.stderr
    )
