"""pilint self-test: every rule proven on fixture snippets (violating and
clean twins), the annotation grammar, then the real tree — tier-1 asserts
`python -m tools.pilint pilosa_tpu/` stays at zero violations, which is
what makes the PR-review invariants machine-enforced instead of
re-derived by eye each round. See docs/static-analysis.md."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from tools.pilint.rules import RepoEnv, build_env  # noqa: E402
from tools.pilint.runner import lint_source, lint_paths  # noqa: E402


def lint(src: str, path: str = "pilosa_tpu/example.py", env: RepoEnv = None,
         rules=None):
    return lint_source(path, textwrap.dedent(src), env or RepoEnv(),
                       rules=rules)


def codes(violations):
    return [v.rule for v in violations]


# ---------------------------------------------------------------- R1


class TestSwallowedExceptions:
    def test_bare_pass_is_violation(self):
        vs = lint("""
            try:
                work()
            except Exception:
                pass
        """, rules=["R1"])
        assert codes(vs) == ["R1"]

    def test_bare_except_is_violation(self):
        vs = lint("""
            try:
                work()
            except:
                pass
        """, rules=["R1"])
        assert codes(vs) == ["R1"]

    def test_narrow_type_is_fine(self):
        vs = lint("""
            try:
                work()
            except KeyError:
                pass
        """, rules=["R1"])
        assert vs == []

    def test_reraise_is_fine(self):
        vs = lint("""
            try:
                work()
            except Exception:
                cleanup()
                raise
        """, rules=["R1"])
        assert vs == []

    def test_log_is_fine(self):
        vs = lint("""
            try:
                work()
            except Exception as e:
                logger.error("failed: %s", e)
        """, rules=["R1"])
        assert vs == []

    def test_counter_increment_is_fine(self):
        vs = lint("""
            try:
                work()
            except Exception:
                counters["errors"] += 1
        """, rules=["R1"])
        assert vs == []

    def test_stats_count_is_fine(self):
        vs = lint("""
            try:
                work()
            except Exception:
                stats.count("WorkError", 1)
        """, rules=["R1"])
        assert vs == []

    def test_captured_error_is_fine(self):
        # collect-and-raise-later (client.py parallel fan-out pattern)
        vs = lint("""
            try:
                work()
            except Exception as e:
                first_error = first_error or e
        """, rules=["R1"])
        assert vs == []

    def test_annotation_suppresses(self):
        vs = lint("""
            try:
                work()
            except Exception:  # pilint: allow-swallow(probe failure means fallback)
                pass
        """)
        assert vs == []

    def test_import_guard_must_catch_importerror(self):
        vs = lint("""
            try:
                import fancy_dep
            except Exception:
                fancy_dep = None
        """, rules=["R1"])
        assert codes(vs) == ["R1"]
        assert "ImportError" in vs[0].message

    def test_import_guard_annotation_does_not_suppress(self):
        vs = lint("""
            try:
                import fancy_dep
            except Exception:  # pilint: allow-swallow(optional dependency)
                fancy_dep = None
        """, rules=["R1"])
        assert codes(vs) == ["R1"]

    def test_importerror_guard_is_fine(self):
        vs = lint("""
            try:
                import fancy_dep
            except ImportError:
                fancy_dep = None
        """, rules=["R1"])
        assert vs == []


# ---------------------------------------------------------------- R2


class TestJaxFreeZones:
    def test_module_level_jax_in_zone(self):
        vs = lint("import jax\n", path="pilosa_tpu/config.py", rules=["R2"])
        assert codes(vs) == ["R2"]

    def test_from_jax_in_zone(self):
        vs = lint("from jax import numpy\n",
                  path="pilosa_tpu/sched/batcher.py", rules=["R2"])
        assert codes(vs) == ["R2"]

    def test_jax_submodule_in_zone(self):
        vs = lint("import jax.numpy as jnp\n",
                  path="pilosa_tpu/tier/__init__.py", rules=["R2"])
        assert codes(vs) == ["R2"]

    def test_function_local_import_is_fine(self):
        vs = lint("""
            def gather():
                import jax
                return jax
        """, path="pilosa_tpu/config.py", rules=["R2"])
        assert vs == []

    def test_type_checking_guard_is_fine(self):
        vs = lint("""
            from typing import TYPE_CHECKING
            if TYPE_CHECKING:
                import jax
        """, path="pilosa_tpu/config.py", rules=["R2"])
        assert vs == []

    def test_type_checking_else_branch_still_checked(self):
        # Only the if-body is typing-only; the else branch runs at import
        # time and must still be a violation in a zone.
        vs = lint("""
            from typing import TYPE_CHECKING
            if TYPE_CHECKING:
                import jax
            else:
                import jax
        """, path="pilosa_tpu/config.py", rules=["R2"])
        assert codes(vs) == ["R2"]

    def test_try_else_and_finally_still_checked(self):
        # Every statement list of a try executes at import time — else
        # and finally included, not just body and handlers.
        vs = lint("""
            try:
                x = 1
            except ImportError:
                x = 2
            else:
                import jax
            finally:
                import jax.numpy
        """, path="pilosa_tpu/config.py", rules=["R2"])
        assert codes(vs) == ["R2", "R2"]

    def test_loop_bodies_still_checked(self):
        vs = lint("""
            for _ in (1,):
                import jax
            while False:
                import jax
            else:
                import jax.numpy
        """, path="pilosa_tpu/config.py", rules=["R2"])
        assert codes(vs) == ["R2", "R2", "R2"]

    def test_outside_zone_is_fine(self):
        vs = lint("import jax\n",
                  path="pilosa_tpu/parallel/engine.py", rules=["R2"])
        assert vs == []

    def test_no_annotation_escape(self):
        vs = lint(
            "import jax  # pilint: allow-swallow(this kind does not apply)\n",
            path="pilosa_tpu/config.py", rules=["R2"])
        assert codes(vs) == ["R2"]


# ---------------------------------------------------------------- R3


class TestBlockingUnderLock:
    def test_sleep_under_lock(self):
        vs = lint("""
            def f(self):
                with self._lock:
                    time.sleep(1)
        """, rules=["R3"])
        assert codes(vs) == ["R3"]

    def test_fsync_under_mutex(self):
        vs = lint("""
            def f(self):
                with self._mu:
                    os.fsync(fd)
        """, rules=["R3"])
        assert codes(vs) == ["R3"]

    def test_device_put_under_lock(self):
        vs = lint("""
            def f(self):
                with self._lock:
                    arr = jax.device_put(x)
        """, rules=["R3"])
        assert codes(vs) == ["R3"]

    def test_sleep_outside_lock_is_fine(self):
        vs = lint("""
            def f(self):
                with self._lock:
                    x = 1
                time.sleep(1)
        """, rules=["R3"])
        assert vs == []

    def test_nested_function_not_flagged(self):
        # the closure runs later, when the lock is not necessarily held
        vs = lint("""
            def f(self):
                with self._lock:
                    def worker():
                        time.sleep(1)
                    return worker
        """, rules=["R3"])
        assert vs == []

    def test_non_lock_with_is_fine(self):
        vs = lint("""
            def f(self):
                with open("x") as fh:
                    time.sleep(1)
        """, rules=["R3"])
        assert vs == []

    def test_annotation_suppresses(self):
        vs = lint("""
            def f(self):
                with self._mu:
                    # pilint: allow-blocking(close boundary, sync must land under the mutex)
                    os.fsync(fd)
        """, rules=["R3"])
        assert vs == []

    def test_module_level_with_lock_still_caught(self):
        # the call-graph walk covers function bodies; module-level lock
        # regions keep the direct lexical scan
        vs = lint("""
            import time
            with _init_lock:
                time.sleep(1)
        """, rules=["R3"])
        assert codes(vs) == ["R3"]

    def test_condition_variable_counts_as_lock(self):
        vs = lint("""
            def f(self):
                with self._demote_cv:
                    time.sleep(1)
        """, rules=["R3"])
        assert codes(vs) == ["R3"]


# ---------------------------------------------------------------- R4


def _env_with_wiring(handler_src: str) -> RepoEnv:
    return build_env({"pilosa_tpu/server/handler.py": textwrap.dedent(handler_src)})


class TestCounterHygiene:
    def test_unwired_counter_in_class_without_snapshot(self):
        vs = lint("""
            class Worker:
                def run(self):
                    self.counters["orphan_counter"] += 1
        """, rules=["R4"])
        assert codes(vs) == ["R4"]
        assert "orphan_counter" in vs[0].message

    def test_wholesale_snapshot_export_is_fine(self):
        vs = lint("""
            class Worker:
                def run(self):
                    self.counters["thing"] += 1
                def snapshot(self):
                    return dict(self.counters)
        """, rules=["R4"])
        assert vs == []

    def test_partial_snapshot_is_not_wholesale(self):
        # A snapshot() exporting a SUBSET must not grant the class R4
        # immunity — the unexported counter is still unobservable.
        vs = lint("""
            class Worker:
                def run(self):
                    self.counters["orphan_counter"] += 1
                def snapshot(self):
                    return {"hits": self.counters["hits"]}
        """, rules=["R4"])
        assert codes(vs) == ["R4"]
        assert "orphan_counter" in vs[0].message

    def test_literal_in_wiring_corpus_is_fine(self):
        env = _env_with_wiring("""
            def handle_debug_vars(self):
                return {"orphan_counter": x.orphan_counter}
        """)
        vs = lint("""
            class Worker:
                def run(self):
                    self.counters["orphan_counter"] += 1
        """, env=env, rules=["R4"])
        assert vs == []

    def test_stats_count_fine_while_wholesale_dump_exists(self):
        env = _env_with_wiring("""
            def handle_debug_vars(self):
                out = stats.snapshot()
                return out
        """)
        vs = lint("""
            def f(stats):
                stats.count("AnythingAtAll", 1)
        """, env=env, rules=["R4"])
        assert vs == []

    def test_stats_count_flagged_without_wholesale_dump(self):
        vs = lint("""
            def f(stats):
                stats.count("LostForever", 1)
        """, rules=["R4"])
        assert codes(vs) == ["R4"]

    def test_annotation_suppresses(self):
        vs = lint("""
            class Worker:
                def run(self):
                    # pilint: allow-counter(test-only counter, asserted directly)
                    self.counters["private"] += 1
        """, rules=["R4"])
        assert vs == []

    def test_nested_class_judged_by_its_own_snapshot(self):
        # A class defined inside a method must not inherit the OUTER
        # class's wholesale-snapshot immunity.
        vs = lint("""
            class Outer:
                def make(self):
                    class Inner:
                        def run(self):
                            self.counters["inner_orphan"] += 1
                    return Inner()
                def snapshot(self):
                    return dict(self.counters)
        """, rules=["R4"])
        assert codes(vs) == ["R4"]
        assert "inner_orphan" in vs[0].message

    def test_nested_class_with_own_snapshot_is_fine(self):
        # ... and a nested class exporting its own counters wholesale is
        # clean even when the enclosing class exports nothing.
        vs = lint("""
            class Outer:
                def make(self):
                    class Inner:
                        def run(self):
                            self.counters["inner_ok"] += 1
                        def snapshot(self):
                            return dict(self.counters)
                    return Inner()
        """, rules=["R4"])
        assert vs == []

    def test_outside_pilosa_tpu_not_checked(self):
        vs = lint("""
            class Worker:
                def run(self):
                    self.counters["whatever"] += 1
        """, path="tools/example.py", rules=["R4"])
        assert vs == []


# ---------------------------------------------------------------- R5


class TestMutationEpochAudit:
    def test_mutation_without_bump(self):
        vs = lint("""
            class Fragment:
                def set_bit(self, pos):
                    return self.storage.add(pos)
        """, path="pilosa_tpu/core/fragment.py", rules=["R5"])
        assert codes(vs) == ["R5"]
        assert "set_bit" in vs[0].message

    def test_direct_generation_bump_is_fine(self):
        vs = lint("""
            class Fragment:
                def set_bit(self, pos):
                    changed = self.storage.add(pos)
                    self.generation += 1
                    return changed
        """, path="pilosa_tpu/core/fragment.py", rules=["R5"])
        assert vs == []

    def test_bump_via_helper_call_walk(self):
        vs = lint("""
            class Fragment:
                def set_bit(self, pos):
                    changed = self.storage.add(pos)
                    self._invalidate(pos)
                    return changed
                def _invalidate(self, pos):
                    self.generation += 1
                    self.epoch.bump()
        """, path="pilosa_tpu/core/fragment.py", rules=["R5"])
        assert vs == []

    def test_epoch_bump_call_is_fine(self):
        vs = lint("""
            class Fragment:
                def read_from(self, f):
                    self.storage.read_from(f)
                    self.epoch.bump()
        """, path="pilosa_tpu/core/fragment.py", rules=["R5"])
        assert vs == []

    def test_outside_core_not_checked(self):
        vs = lint("""
            class Thing:
                def mutate(self):
                    self.storage.add(1)
        """, path="pilosa_tpu/tier/manager.py", rules=["R5"])
        assert vs == []

    def test_annotation_suppresses(self):
        vs = lint("""
            class Fragment:
                # pilint: allow-mutation(recovery replay runs before any reader exists)
                def _replay(self, data):
                    self.storage.read_from(data)
        """, path="pilosa_tpu/core/fragment.py", rules=["R5"])
        assert vs == []


# ---------------------------------------------------------------- R6


class TestFailpointHygiene:
    def _env(self, docs=("wal-append",), fires=()):
        env = RepoEnv()
        env.failpoint_docs_loaded = True
        env.failpoint_doc_names = set(docs)
        env.failpoint_fire_sites = set(fires)
        return env

    def test_undocumented_fire_site_is_violation(self):
        vs = lint("""
            from . import failpoints

            def append(self):
                failpoints.fire("wal-apend")
        """, env=self._env(), rules=["R6"])
        assert codes(vs) == ["R6"]
        assert "wal-apend" in vs[0].message

    def test_documented_fire_site_is_fine(self):
        vs = lint("""
            from . import failpoints

            def append(self):
                failpoints.fire("wal-append")
        """, env=self._env(), rules=["R6"])
        assert vs == []

    def test_targeted_fire_site_checks_base_name(self):
        # fire() passes the target as a kwarg, so the literal IS the base
        # name — a documented name with a target kwarg stays clean.
        vs = lint("""
            from . import failpoints

            def send(self, netloc):
                failpoints.fire("wal-append", target=netloc)
        """, env=self._env(), rules=["R6"])
        assert vs == []

    def test_annotation_suppresses_fire_site(self):
        vs = lint("""
            from . import failpoints

            def append(self):
                # pilint: allow-failpoint(internal-only point, not for tests)
                failpoints.fire("secret-point")
        """, env=self._env(), rules=["R6"])
        assert vs == []

    def test_docs_not_loaded_no_ops(self):
        # Fixture/snippet runs without the docs corpus must not flag.
        env = RepoEnv()
        vs = lint("""
            from . import failpoints

            def append(self):
                failpoints.fire("whatever")
        """, env=env, rules=["R6"])
        assert vs == []

    def test_outside_pilosa_tpu_not_checked(self):
        vs = lint("""
            def f():
                fire("not-a-real-point")
        """, path="scripts/tool.py", env=self._env(), rules=["R6"])
        assert vs == []

    def test_orphan_spec_in_test_is_violation(self):
        from tools.pilint.rules import (collect_spec_sites,
                                        failpoint_orphan_violations)

        env = self._env(fires={"wal-append"})
        env.failpoint_spec_sites = collect_spec_sites(
            "tests/test_x.py", textwrap.dedent("""
                import os
                os.environ["PILOSA_TPU_FAILPOINTS"] = "wal-apend=error"
            """))
        vs = failpoint_orphan_violations(env)
        assert codes(vs) == ["R6"]
        assert "wal-apend" in vs[0].message

    def test_spec_with_fire_site_is_fine(self):
        from tools.pilint.rules import (collect_spec_sites,
                                        failpoint_orphan_violations)

        env = self._env(fires={"wal-append", "client-send"})
        env.failpoint_spec_sites = collect_spec_sites(
            "tests/test_x.py", textwrap.dedent("""
                SPEC = "wal-append=1*crash;client-send@localhost:1=drop"
                failpoints.configure("client-send", "latency", arg=5)
            """))
        assert failpoint_orphan_violations(env) == []

    def test_configure_collected_and_target_stripped(self):
        from tools.pilint.rules import collect_spec_sites

        sites = collect_spec_sites(
            "tests/test_x.py", textwrap.dedent("""
                failpoints.configure("migrate-begin@host:1", "error")
            """))
        assert [n for _, _, n in sites] == ["migrate-begin"]

    def test_allow_failpoint_annotation_excludes_spec(self):
        from tools.pilint.rules import collect_spec_sites

        sites = collect_spec_sites(
            "tests/test_x.py", textwrap.dedent("""
                failpoints.configure("p", "error")  # pilint: allow-failpoint(registry grammar test)
            """))
        assert sites == []

    def test_plain_assignment_string_not_a_spec(self):
        # Ordinary key=value literals must not parse as activation specs.
        from tools.pilint.rules import collect_spec_sites

        sites = collect_spec_sites(
            "tests/test_x.py", 'H = "content-type=application/json"\n')
        assert sites == []

    def test_docs_table_parser_reads_section_rows(self):
        from tools.pilint.rules import parse_failpoint_docs

        names = parse_failpoint_docs(textwrap.dedent("""
            ## Something else

            | `not-a-point` | x |

            ## Failpoints (`pilosa_tpu/failpoints.py`)

            | failpoint | fires at |
            |---|---|
            | `wal-append` | WAL append |
            | `device-dispatch` | engine dispatch |

            ## After

            | `also-not` | y |
        """))
        assert names == {"wal-append", "device-dispatch"}

    def test_real_tree_docs_cover_every_fire_site(self):
        """Belt and braces over the zero-violations test: the shipped
        docs table and the shipped fire sites agree exactly on names."""
        from tools.pilint.rules import (collect_fire_names,
                                        parse_failpoint_docs)
        import ast, glob

        with open(os.path.join(REPO_ROOT, "docs", "durability.md")) as f:
            doc_names = parse_failpoint_docs(f.read())
        fired = set()
        for path in glob.glob(
                os.path.join(REPO_ROOT, "pilosa_tpu", "**", "*.py"),
                recursive=True):
            with open(path) as f:
                fired |= collect_fire_names(ast.parse(f.read()))
        assert fired, "no fire sites found — collection broke"
        assert fired <= doc_names, fired - doc_names


# ---------------------------------------------------------------- R7


class TestSpanHygiene:
    def _env(self, docs=("parse", "gather"), records=("parse", "gather")):
        env = RepoEnv()
        env.span_docs_loaded = True
        env.span_doc_names = set(docs)
        env.span_record_sites = set(records)
        return env

    def test_undocumented_span_site_is_violation(self):
        vs = lint("""
            from ..obs import span as obs_span

            def f():
                with obs_span("gathr"):
                    work()
        """, env=self._env(), rules=["R7"])
        assert codes(vs) == ["R7"]

    def test_documented_span_site_is_fine(self):
        vs = lint("""
            from ..obs import span as obs_span, record as obs_record

            def f():
                with obs_span("gather"):
                    work()
                obs_record("parse", 1.0)
        """, env=self._env(), rules=["R7"])
        assert vs == []

    def test_dynamic_span_name_not_checked(self):
        # remote:<peer> hops are f-strings: statically unverifiable,
        # documented for humans, never a violation.
        vs = lint("""
            def f(trace, target):
                with trace.span(f"remote:{target.id}"):
                    work()
        """, env=self._env(), rules=["R7"])
        assert vs == []

    def test_annotation_suppresses_span_site(self):
        vs = lint("""
            from ..obs import span as obs_span

            def f():
                # pilint: allow-span(internal-only stage, not operator-facing)
                with obs_span("secret.stage"):
                    work()
        """, env=self._env(), rules=["R7"])
        assert vs == []

    def test_docs_not_loaded_no_ops(self):
        env = RepoEnv()  # span_docs_loaded stays False
        vs = lint("""
            from ..obs import span as obs_span

            def f():
                with obs_span("whatever"):
                    work()
        """, env=env, rules=["R7"])
        assert vs == []

    def test_outside_pilosa_tpu_not_checked(self):
        vs = lint("""
            span("anything-goes")
        """, path="scripts/tool.py", env=self._env(), rules=["R7"])
        assert vs == []

    def test_orphan_asserted_span_is_violation(self):
        from tools.pilint.rules import (collect_span_assert_sites,
                                        span_orphan_violations)

        env = self._env(records=("parse",))
        env.span_assert_sites = collect_span_assert_sites(
            "tests/test_x.py", textwrap.dedent("""
                def test_t(trace):
                    find_span(trace, "gathr")  # pilint: allow-span(fixture negative for this self-test)

                    assert_span(trace, "gathre")
            """))
        vs = span_orphan_violations(env)
        assert codes(vs) == ["R7"]
        assert "gathre" in vs[0].message

    def test_asserted_span_with_record_site_is_fine(self):
        from tools.pilint.rules import (collect_span_assert_sites,
                                        span_orphan_violations)

        env = self._env(records=("parse", "gather"))
        env.span_assert_sites = collect_span_assert_sites(
            "tests/test_x.py", textwrap.dedent("""
                def test_t(trace):
                    assert_span(trace, "gather")
            """))
        assert span_orphan_violations(env) == []

    def test_docs_table_parser_reads_span_section(self):
        from tools.pilint.rules import parse_span_docs

        names = parse_span_docs(textwrap.dedent("""
            ## Something else

            | `not-a-span` | x |

            ## Span reference

            | span | recorded at |
            |---|---|
            | `parse` | executor |
            | `remote:<peer>` | client hop |

            ## After

            | `also-not` | y |
        """))
        assert names == {"parse", "remote:<peer>"}

    def test_real_tree_docs_cover_every_span_site(self):
        """The shipped span table and the shipped recording sites agree:
        every constant span name recorded anywhere in pilosa_tpu/ has a
        row in docs/observability.md."""
        from tools.pilint.rules import collect_span_names, parse_span_docs
        import ast, glob

        with open(os.path.join(REPO_ROOT, "docs", "observability.md")) as f:
            doc_names = parse_span_docs(f.read())
        recorded = set()
        for path in glob.glob(
                os.path.join(REPO_ROOT, "pilosa_tpu", "**", "*.py"),
                recursive=True):
            with open(path) as f:
                recorded |= collect_span_names(ast.parse(f.read()))
        assert recorded, "no span recording sites found — collection broke"
        assert recorded <= doc_names, recorded - doc_names
        # And every acceptance stage actually records somewhere.
        for name in ("parse", "sched.wait", "batch.hold", "executor.fanout",
                     "gather", "device.dispatch", "tier.promote", "reduce"):
            assert name in recorded, name


# ------------------------------------------------------- annotation grammar


class TestAnnotationGrammar:
    def test_unknown_kind_is_violation(self):
        vs = lint("x = 1  # pilint: allow-everything(just because)\n")
        assert [v.rule for v in vs] == ["A0"]

    def test_empty_reason_is_violation(self):
        vs = lint("""
            try:
                work()
            except Exception:  # pilint: allow-swallow()
                pass
        """, rules=None)
        # the annotation still suppresses R1 (one finding per problem),
        # but the missing reason is itself flagged
        assert [v.rule for v in vs] == ["A0"]

    def test_short_reason_is_violation(self):
        vs = lint("""
            try:
                work()
            except Exception:  # pilint: allow-swallow(ok)
                pass
        """)
        assert [v.rule for v in vs] == ["A0"]

    def test_unused_annotation_is_violation(self):
        vs = lint("x = 1  # pilint: allow-swallow(nothing here swallows)\n")
        assert [v.rule for v in vs] == ["A0"]
        assert "unused" in vs[0].message

    def test_unused_blocking_annotation_exempt_when_covering_a_call(self):
        # consumed by the runtime lock checker, which honors any frame of
        # a blocking stack — possible only where a call crosses the line
        vs = lint("""
            def f(self):
                # pilint: allow-blocking(runtime-only lock context)
                self._helper_that_blocks()
        """)
        assert vs == []

    def test_unused_blocking_annotation_rot_without_any_call(self):
        # v2 narrowing (the annotation-rot sweep): no call crosses the
        # covered lines, so neither the static pass nor the runtime
        # checker can ever consume it — provably stale, delete it.
        vs = lint("x = 1  # pilint: allow-blocking(refactor left me behind)\n")
        assert [v.rule for v in vs] == ["A0"]
        assert "runtime lock checker" in vs[0].message

    def test_annotation_in_docstring_is_not_an_annotation(self):
        # lockcheck.py documents the grammar in prose; a spelling inside
        # a string literal must parse as neither annotation nor rot.
        vs = lint('''
            def f():
                """Suppress with `# pilint: allow-blocking(reason)` on the line."""
                return 1
        ''')
        assert vs == []

    def test_annotation_on_line_above(self):
        vs = lint("""
            try:
                work()
            # pilint: allow-swallow(reason lives on the line above)
            except Exception:
                pass
        """)
        assert vs == []


# ------------------------------------------------------------- real tree


class TestRealTree:
    def test_pilosa_tpu_is_clean(self):
        """THE enforcement test: the shipped tree has zero unannotated
        violations. A new swallowed except / jax import in a config
        module / blocking call under a lock / orphaned counter fails
        tier-1, not a human reviewer's attention."""
        vs = lint_paths([os.path.join(REPO_ROOT, "pilosa_tpu")],
                        repo_root=REPO_ROOT)
        assert vs == [], "\n".join(str(v) for v in vs)

    def test_cli_entry_exits_zero_on_clean_tree(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.pilint", "pilosa_tpu/"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "0 violations" in proc.stdout

    def test_cli_entry_exits_nonzero_on_violation(self, tmp_path):
        bad = tmp_path / "pilosa_tpu"
        bad.mkdir()
        (bad / "bad.py").write_text(
            "try:\n    work()\nexcept Exception:\n    pass\n")
        proc = subprocess.run(
            [sys.executable, "-m", "tools.pilint", str(bad)],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 1
        assert "R1" in proc.stdout

    def test_cli_list_rules(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.pilint", "--list-rules"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=60,
        )
        assert proc.returncode == 0
        for rule_id in ("R1", "R2", "R3", "R4", "R5"):
            assert rule_id in proc.stdout

    def test_every_annotation_carries_reason(self):
        """Acceptance criterion: every allow-* annotation in the tree has
        a human-readable reason (the A0 grammar checks run with the full
        rule set in test_pilosa_tpu_is_clean; this asserts the grammar is
        actually exercised — the tree DOES contain annotations)."""
        from tools.pilint.core import parse_annotations

        total = 0
        for root, _dirs, files in os.walk(os.path.join(REPO_ROOT, "pilosa_tpu")):
            for name in files:
                if not name.endswith(".py"):
                    continue
                full = os.path.join(root, name)
                with open(full, "r", encoding="utf-8") as f:
                    annotations, grammar_violations = parse_annotations(
                        full, f.read())
                assert grammar_violations == [], grammar_violations
                total += len(annotations)
                for a in annotations:
                    assert len(a.reason) >= 4, (full, a)
        assert total > 0, "expected the tree to carry pilint annotations"


# ----------------------------------------------- interprocedural lock flow


class TestInterproceduralLockFlow:
    """R3's v2 half: may-hold-lock propagation through resolved call
    edges (tools/pilint/graph.py), config-bounded depth."""

    def test_helper_blocking_caught_at_depth_one(self):
        vs = lint("""
            import os

            class W:
                def commit(self):
                    with self._mu:
                        self._persist()
                def _persist(self):
                    os.fsync(self._fd)
        """, rules=["R3"])
        assert codes(vs) == ["R3"]
        assert "reached while a lock is held" in vs[0].message
        assert "_persist" in vs[0].message

    def test_module_function_helper_caught(self):
        vs = lint("""
            import os

            def persist(fd):
                os.fsync(fd)

            class W:
                def commit(self):
                    with self._mu:
                        persist(self._fd)
        """, rules=["R3"])
        assert codes(vs) == ["R3"]

    def test_caught_at_the_depth_limit(self):
        # chain: with -> h1 -> h2 -> h3 -> h4(fsync): 4 call edges = the
        # default depth limit, still caught...
        src = """
            import os

            class W:
                def commit(self):
                    with self._mu:
                        self._h1()
                def _h1(self):
                    self._h2()
                def _h2(self):
                    self._h3()
                def _h3(self):
                    self._h4()
                def _h4(self):
                    os.fsync(self._fd)
        """
        vs = lint(src, rules=["R3"])
        assert codes(vs) == ["R3"]

    def test_beyond_the_depth_limit_not_caught(self):
        # ...and one helper deeper than the configured limit is out of
        # reach (the limit is the soundness/noise dial, CLI --depth).
        src = """
            import os

            class W:
                def commit(self):
                    with self._mu:
                        self._h1()
                def _h1(self):
                    self._h2()
                def _h2(self):
                    self._h3(self)
                def _h3(self, x):
                    os.fsync(self._fd)
        """
        assert codes(lint(src, rules=["R3"])) == ["R3"]
        vs = lint_source("pilosa_tpu/example.py", textwrap.dedent(src),
                         RepoEnv(), rules=["R3"], depth=2)
        assert vs == []

    def test_recursion_cycle_terminates(self):
        vs = lint("""
            import os

            class W:
                def commit(self):
                    with self._mu:
                        self._a()
                def _a(self):
                    self._b()
                def _b(self):
                    self._a()
                    os.fsync(self._fd)
        """, rules=["R3"])
        assert codes(vs) == ["R3"]

    def test_annotation_on_the_caller_vouches_for_the_callee(self):
        # the lock-holding caller takes responsibility for the callee
        # subtree, mirroring lockcheck's any-frame suppression
        vs = lint("""
            import os

            class W:
                def commit(self):
                    with self._mu:
                        # pilint: allow-blocking(tiny checkpoint, ordered with the ack by design)
                        self._persist()
                def _persist(self):
                    os.fsync(self._fd)
        """, rules=["R3"])
        assert vs == []

    def test_annotation_on_the_deny_line_still_suppresses(self):
        vs = lint("""
            import os

            class W:
                def commit(self):
                    with self._mu:
                        self._persist()
                def _persist(self):
                    # pilint: allow-blocking(close boundary, sync must land under the mutex)
                    os.fsync(self._fd)
        """, rules=["R3"])
        assert vs == []

    def test_import_fallback_def_in_except_body_is_visible(self):
        # a def nested inside an except-handler (the import-fallback
        # idiom) must still be a call-graph node — blocking host helpers
        # live exactly there
        vs = lint("""
            import os

            try:
                from fastlib import persist
            except ImportError:
                def persist(fd):
                    os.fsync(fd)

            class W:
                def commit(self):
                    with self._mu:
                        persist(self._fd)
        """, rules=["R3"])
        assert codes(vs) == ["R3"]

    def test_module_level_region_seeds_module_function_helper(self):
        # a module-level `with _boot_lock:` reaches a helper's fsync too
        vs = lint("""
            import os

            def _warm(fd):
                os.fsync(fd)

            with _boot_lock:
                _warm(3)
        """, rules=["R3"])
        assert codes(vs) == ["R3"]
        assert "reached while a lock is held" in vs[0].message

    def test_nested_def_in_helper_not_lock_attributed(self):
        # a worker closure defined (not called) in the helper runs later
        vs = lint("""
            import os

            class W:
                def commit(self):
                    with self._mu:
                        self._persist()
                def _persist(self):
                    def later():
                        os.fsync(self._fd)
                    return later
        """, rules=["R3"])
        assert vs == []

    def test_direct_and_helper_hits_both_reported(self):
        vs = lint("""
            import os, time

            class W:
                def commit(self):
                    with self._mu:
                        time.sleep(0.1)
                        self._persist()
                def _persist(self):
                    os.fsync(self._fd)
        """, rules=["R3"])
        assert codes(vs) == ["R3", "R3"]


# ---------------------------------------------------------------- R8


class TestGuardedMaterialization:
    ENGINE = "pilosa_tpu/parallel/engine.py"
    COLLECTIVE = "pilosa_tpu/parallel/collective.py"

    def test_forcing_guard_result_outside_guard(self):
        vs = lint("""
            import numpy as np

            class Engine:
                def count_batch(self, leaves):
                    fn = self._fn_build(self._fns, ("sig",), self._build)
                    arr = self._device_call(("sig",), lambda: fn(leaves))
                    return np.asarray(arr)[:4]
        """, path=self.ENGINE, rules=["R8"])
        assert codes(vs) == ["R8"]
        assert "asarray" in vs[0].message

    def test_forcing_inside_the_guard_thunk_is_fine(self):
        vs = lint("""
            import numpy as np

            class Engine:
                def count_batch(self, leaves):
                    fn = self._fn_build(self._fns, ("sig",), self._build)
                    return self._device_call(
                        ("sig",), lambda: np.asarray(fn(leaves))[:4])
        """, path=self.ENGINE, rules=["R8"])
        assert vs == []

    def test_block_until_ready_outside_guard(self):
        vs = lint("""
            class Engine:
                def bitmap(self, leaves):
                    fn = self._fn(("sig",), self._build)
                    planes = self._device_call(("sig",), lambda: fn(leaves))
                    return planes.block_until_ready()
        """, path=self.ENGINE, rules=["R8"])
        assert codes(vs) == ["R8"]

    def test_block_until_ready_inside_guard_is_fine(self):
        vs = lint("""
            class Engine:
                def bitmap(self, leaves):
                    fn = self._fn(("sig",), self._build)
                    return self._device_call(
                        ("sig",), lambda: fn(leaves).block_until_ready())
        """, path=self.ENGINE, rules=["R8"])
        assert vs == []

    def test_tainted_returning_helper_forced_outside_guard(self):
        # count_batch_async returns the unmaterialized array BY DESIGN;
        # a caller forcing it outside the guard is the bug
        vs = lint("""
            import numpy as np

            class Engine:
                def count_async(self, leaves):
                    fn = self._fn_build(self._fns, ("sig",), self._build)
                    return self._device_call(("sig",), lambda: fn(leaves))
                def count(self, leaves):
                    return np.asarray(self.count_async(leaves))
        """, path=self.ENGINE, rules=["R8"])
        assert codes(vs) == ["R8"]

    def test_helper_dominated_by_ladder_root_is_fine(self):
        # collective: _run_count materializes, but is reached only from
        # _enter (the runner-thread ladder) — guarded interprocedurally
        vs = lint("""
            import numpy as np

            class Backend:
                def _enter(self, desc):
                    return self._run_count(desc)
                def _run_count(self, desc):
                    fn = self._fn(("sig",), self._build)
                    lo, hi = fn(desc)
                    return np.asarray(lo), np.asarray(hi)
        """, path=self.COLLECTIVE, rules=["R8"])
        assert vs == []

    def test_same_shape_not_dominated_is_flagged(self):
        # identical body, but reachable from a public method too: the
        # materialization can execute outside the ladder
        vs = lint("""
            import numpy as np

            class Backend:
                def preview(self, desc):
                    return self._run_count(desc)
                def _run_count(self, desc):
                    fn = self._fn(("sig",), self._build)
                    lo, hi = fn(desc)
                    return np.asarray(lo), np.asarray(hi)
        """, path=self.COLLECTIVE, rules=["R8"])
        assert codes(vs) == ["R8", "R8"]

    def test_named_def_thunk_passed_to_guard_is_fine(self):
        vs = lint("""
            import numpy as np

            class Engine:
                def topn(self, rows):
                    fn = self._fn_build(self._fns, ("sig",), self._build)
                    def run():
                        return np.asarray(fn(rows))[:2]
                    return self._device_call(None, run)
        """, path=self.ENGINE, rules=["R8"])
        assert vs == []

    def test_helper_called_only_from_guard_lambda_is_dominated(self):
        # the helper's one call site lives INSIDE a guard thunk, so its
        # materialization executes under the ladder — not a finding
        vs = lint("""
            import numpy as np

            class Engine:
                def _pull(self, fn, leaves):
                    return np.asarray(fn(leaves))
                def count(self, leaves):
                    fn = self._fn(("sig",), self._build)
                    return self._device_call(
                        ("sig",), lambda: self._pull(fn, leaves))
        """, path=self.ENGINE, rules=["R8"])
        assert vs == []

    def test_host_input_asarray_untainted(self):
        vs = lint("""
            import numpy as np

            class Engine:
                def topn(self, row_ids):
                    req = np.asarray(row_ids)
                    return req
        """, path=self.ENGINE, rules=["R8"])
        assert vs == []

    def test_outside_dispatch_modules_not_checked(self):
        vs = lint("""
            import numpy as np

            class X:
                def f(self, leaves):
                    fn = self._fn(("sig",), self._build)
                    return np.asarray(fn(leaves))
        """, path="pilosa_tpu/executor.py", rules=["R8"])
        assert vs == []

    def test_annotation_suppresses(self):
        vs = lint("""
            import numpy as np

            class Engine:
                def count(self, leaves):
                    fn = self._fn(("sig",), self._build)
                    # pilint: allow-materialize(startup warm path, faults handled by caller)
                    return np.asarray(fn(leaves))
        """, path=self.ENGINE, rules=["R8"])
        assert vs == []


# ---------------------------------------------------------------- R9


class TestProbeClaimHygiene:
    HEALTH = "pilosa_tpu/parallel/device_health.py"

    BUG = """
        class H:
            def plan(self, sig):
                now = self.clock()
                s = self._sigs.get(sig)
                gate = self._gate_locked(self._plane, now)
                if gate is False:
                    return "host"
                if s is not None:
                    if self._gate_locked(s, now) is False:
                        return "host"
                return "device"
            def _gate_locked(self, b, now):
                b.probe_at = now
                return True
    """

    def test_claim_before_due_check_is_violation(self):
        vs = lint(self.BUG, path=self.HEALTH, rules=["R9"])
        assert codes(vs) == ["R9"]
        assert "orphans the claimed probe" in vs[0].message

    def test_due_check_before_first_claim_is_fine(self):
        vs = lint("""
            class H:
                def plan(self, sig):
                    now = self.clock()
                    s = self._sigs.get(sig)
                    if s is not None and not self._due_locked(s, now):
                        return "host"
                    gate = self._gate_locked(self._plane, now)
                    if gate is False:
                        return "host"
                    if s is not None:
                        self._gate_locked(s, now)
                    return "device"
                def _due_locked(self, b, now):
                    return now - b.probe_at >= 1.0
                def _gate_locked(self, b, now):
                    b.probe_at = now
                    return True
        """, path=self.HEALTH, rules=["R9"])
        assert vs == []

    def test_single_claim_site_is_fine(self):
        # one breaker involved: nothing to orphan by short-circuiting
        vs = lint("""
            class H:
                def allow_request(self, node_id):
                    return self._gate_locked(self._peer(node_id), 0.0)
                def _gate_locked(self, b, now):
                    b.probe_at = now
                    return True
        """, path=self.HEALTH, rules=["R9"])
        assert vs == []

    def test_outside_health_modules_not_checked(self):
        vs = lint(self.BUG, path="pilosa_tpu/executor.py", rules=["R9"])
        assert vs == []

    def test_annotation_suppresses(self):
        vs = lint("""
            class H:
                def plan(self, sig):
                    now = self.clock()
                    # pilint: allow-probe(single-breaker path: the second claim is unreachable with sig=None)
                    gate = self._gate_locked(self._plane, now)
                    if gate is False:
                        return "host"
                    self._gate_locked(self._sigs[sig], now)
                    return "device"
                def _gate_locked(self, b, now):
                    b.probe_at = now
                    return True
        """, path=self.HEALTH, rules=["R9"])
        assert vs == []


# ---------------------------------------------------------------- R10


class TestNoneGuardedStats:
    def test_unguarded_holder_stats_count(self):
        vs = lint("""
            class Executor:
                def f(self):
                    self.holder.stats.count("X", 1)
        """, rules=["R10"])
        assert codes(vs) == ["R10"]
        assert "self.holder.stats" in vs[0].message

    def test_if_truthy_guard_is_fine(self):
        vs = lint("""
            class Executor:
                def f(self):
                    if self.holder.stats:
                        self.holder.stats.count("X", 1)
        """, rules=["R10"])
        assert vs == []

    def test_is_not_none_guard_is_fine(self):
        vs = lint("""
            class Executor:
                def _count_stat(self, name):
                    if self.holder.stats is not None:
                        self.holder.stats.count(name, 1)
        """, rules=["R10"])
        assert vs == []

    def test_early_return_bailout_is_fine(self):
        vs = lint("""
            class Executor:
                def f(self):
                    if self.holder.stats is None:
                        return
                    self.holder.stats.count("X", 1)
        """, rules=["R10"])
        assert vs == []

    def test_and_guard_is_fine(self):
        vs = lint("""
            class T:
                def stop(self):
                    self.stats and self.stats.timing("Q", 1.0)
        """, rules=["R10"])
        assert vs == []

    def test_guard_of_a_different_chain_does_not_count(self):
        vs = lint("""
            class Executor:
                def f(self):
                    if self.other.stats:
                        self.holder.stats.count("X", 1)
        """, rules=["R10"])
        assert codes(vs) == ["R10"]

    def test_timing_checked_too(self):
        vs = lint("""
            class T:
                def stop(self):
                    self.stats.timing("Q", 1.0)
        """, rules=["R10"])
        assert codes(vs) == ["R10"]

    def test_ctor_coalesced_self_stats_is_never_none(self):
        # Server.stats = stats or InMemoryStatsClient(): that holder is
        # never stats-less, no guard needed
        vs = lint("""
            class Server:
                def __init__(self, stats=None):
                    self.stats = stats or InMemoryStatsClient()
                def tick(self):
                    self.stats.count("AntiEntropy", 1)
        """, rules=["R10"])
        assert vs == []

    def test_annotated_coalescing_assignment_also_counts(self):
        # ast.AnnAssign, not ast.Assign — the annotation must not hide
        # the coalescing from the nullability analysis
        vs = lint("""
            class Server:
                def __init__(self, stats=None):
                    self.stats: object = stats or InMemoryStatsClient()
                def tick(self):
                    self.stats.count("AntiEntropy", 1)
        """, rules=["R10"])
        assert vs == []

    def test_plain_ctor_assignment_stays_nullable(self):
        vs = lint("""
            class Fragment:
                def __init__(self, stats=None):
                    self.stats = stats
                def set_bit(self):
                    self.stats.count("setBit", 1)
        """, rules=["R10"])
        assert codes(vs) == ["R10"]

    def test_outside_pilosa_tpu_not_checked(self):
        vs = lint("""
            stats.count("X", 1)
        """, path="scripts/tool.py", rules=["R10"])
        assert vs == []

    def test_annotation_suppresses(self):
        vs = lint("""
            class Executor:
                def f(self):
                    # pilint: allow-stat(test-only executor, holder always carries stats here)
                    self.holder.stats.count("X", 1)
        """, rules=["R10"])
        assert vs == []


# ---------------------------------------------------------------- R11


def _r11_env(constants=(), cli=(), docs="", set_attrs=(), dump_rows=None):
    env = RepoEnv()
    env.config_surface_loaded = True
    env.config_constants = set(constants)
    env.cli_constants = set(cli)
    env.config_docs = {"docs/engine-caches.md": docs}
    env.config_set_attrs = set(set_attrs)
    env.config_dump_rows = dict(dump_rows or {})
    return env


_R11_FULL = dict(
    constants={"ENGINE_GATHER_WORKERS", "engine_gather_workers",
               "ENGINE_PLAN_CACHE", "engine_plan_cache"},
    cli={"--engine-gather-workers", "--engine-plan-cache"},
    docs="knobs: `gather-workers` and `plan-cache` do things",
    set_attrs={"self.engine.gather_workers", "self.engine.plan_cache"},
    dump_rows={"engine": {"gather-workers = ", "plan-cache = "}},
)


class TestConfigSurface:
    SRC = """
        from dataclasses import dataclass

        @dataclass
        class EngineConfig:
            gather_workers: int = 0
            plan_cache: int = 1
    """

    def test_complete_surface_is_fine(self):
        vs = lint(self.SRC, path="pilosa_tpu/parallel/__init__.py",
                  env=_r11_env(**_R11_FULL), rules=["R11"])
        assert vs == []

    def test_missing_surfaces_listed(self):
        partial = dict(_R11_FULL)
        partial["dump_rows"] = {"engine": {"gather-workers = "}}
        partial["docs"] = "only `gather-workers` here"
        vs = lint(self.SRC, path="pilosa_tpu/parallel/__init__.py",
                  env=_r11_env(**partial), rules=["R11"])
        assert codes(vs) == ["R11"]
        assert "plan_cache" in vs[0].message
        assert "to_toml" in vs[0].message
        assert "docs/engine-caches.md" in vs[0].message
        assert "gather_workers" not in vs[0].message

    def test_shared_key_in_another_section_does_not_mask_drift(self):
        # `delta-max-fraction` exists in BOTH [engine] and [collective];
        # a dump row present only under the OTHER section's header must
        # not satisfy this section's check (the masking bug class)
        masked = dict(_R11_FULL)
        masked["dump_rows"] = {"engine": {"gather-workers = "},
                               "collective": {"plan-cache = "}}
        vs = lint(self.SRC, path="pilosa_tpu/parallel/__init__.py",
                  env=_r11_env(**masked), rules=["R11"])
        assert codes(vs) == ["R11"]
        assert "plan_cache" in vs[0].message and "to_toml" in vs[0].message

    def test_parse_store_scoped_to_section(self):
        # another section parsing the same field name must not count
        unparsed = dict(_R11_FULL)
        unparsed["set_attrs"] = {"self.engine.gather_workers",
                                 "self.collective.plan_cache"}
        vs = lint(self.SRC, path="pilosa_tpu/parallel/__init__.py",
                  env=_r11_env(**unparsed), rules=["R11"])
        assert codes(vs) == ["R11"]
        assert "_apply_dict" in vs[0].message

    def test_env_not_loaded_no_ops(self):
        vs = lint(self.SRC, path="pilosa_tpu/parallel/__init__.py",
                  env=RepoEnv(), rules=["R11"])
        assert vs == []

    def test_non_section_dataclass_not_checked(self):
        vs = lint("""
            from dataclasses import dataclass

            @dataclass
            class SomethingElseConfig:
                whatever: int = 0
        """, path="pilosa_tpu/parallel/__init__.py",
                  env=_r11_env(**_R11_FULL), rules=["R11"])
        assert vs == []

    def test_underscore_field_skipped(self):
        vs = lint("""
            from dataclasses import dataclass

            @dataclass
            class EngineConfig:
                _internal: int = 0
        """, path="pilosa_tpu/parallel/__init__.py",
                  env=_r11_env(**_R11_FULL), rules=["R11"])
        assert vs == []

    def test_annotation_suppresses(self):
        vs = lint("""
            from dataclasses import dataclass

            @dataclass
            class EngineConfig:
                # pilint: allow-config(internal tuning knob, deliberately off the operator surface)
                secret_knob: int = 0
        """, path="pilosa_tpu/parallel/__init__.py",
                  env=_r11_env(**_R11_FULL), rules=["R11"])
        assert vs == []

    def test_real_tree_surface_is_complete(self):
        """Belt and braces over the zero-violations test: rebuild the
        R11 corpus from the shipped config.py/cli.py/docs and assert
        every section dataclass field reaches every surface."""
        vs = lint_paths([os.path.join(REPO_ROOT, "pilosa_tpu")],
                        repo_root=REPO_ROOT, rules=["R11"])
        assert vs == [], "\\n".join(str(v) for v in vs)


# ------------------------------------------------- reverted-fix corpus


CORPUS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "fixtures", "pilint_corpus")

# fixture stem -> (pretend repo path, rule). The pretend path routes the
# fixture into the right rule scope (R8 judges the dispatch modules, R9
# the health modules, ...).
CORPUS = {
    "r3_helper_blocking": ("pilosa_tpu/tier/manager.py", "R3"),
    "r8_unguarded_materialization": ("pilosa_tpu/parallel/engine.py", "R8"),
    "r9_device_probe": ("pilosa_tpu/parallel/device_health.py", "R9"),
    "r9_collective_probe": ("pilosa_tpu/parallel/device_health.py", "R9"),
    "r10_unguarded_stat": ("pilosa_tpu/executor.py", "R10"),
    "r11_config_drift": ("pilosa_tpu/parallel/__init__.py", "R11"),
}

_R11_DRIFT_FULL = dict(
    constants={"ENGINE_GATHER_WORKERS", "engine_gather_workers",
               "ENGINE_PLAN_CACHE", "engine_plan_cache"},
    cli={"--engine-gather-workers", "--engine-plan-cache"},
    set_attrs={"self.engine.gather_workers", "self.engine.plan_cache"},
)


class TestRevertedFixCorpus:
    """THE acceptance corpus: every PR 8/9/12 review-round bug, reverted
    back into a fixture, is flagged by exactly its rule — and every
    clean twin (the shape the fix shipped) passes. A rule regression
    that would let one of these shapes back into review fails here."""

    def _lint_fixture(self, stem, suffix, rule):
        path, _ = CORPUS[stem]
        full = os.path.join(CORPUS_DIR, f"{stem}_{suffix}.py")
        with open(full, "r", encoding="utf-8") as f:
            src = f.read()
        if rule == "R11":
            # the drift fixture reconstructs plan-cache missing from the
            # dump + doc; the clean twin gets the full surface corpus
            docs = ("`gather-workers` `plan-cache`" if suffix == "clean"
                    else "`gather-workers` only")
            rows = {"engine": {"gather-workers = ", "plan-cache = "}}
            if suffix == "bug":
                rows = {"engine": {"gather-workers = "}}
            env = _r11_env(constants=_R11_DRIFT_FULL["constants"],
                           cli=_R11_DRIFT_FULL["cli"], docs=docs,
                           set_attrs=_R11_DRIFT_FULL["set_attrs"],
                           dump_rows=rows)
        else:
            env = RepoEnv()
        return lint_source(path, src, env, rules=[rule])

    @pytest.mark.parametrize("stem", sorted(CORPUS))
    def test_bug_fixture_is_flagged(self, stem):
        _, rule = CORPUS[stem]
        vs = self._lint_fixture(stem, "bug", rule)
        assert vs, f"{stem}_bug.py: expected {rule} findings, got none"
        assert {v.rule for v in vs} == {rule}, vs

    @pytest.mark.parametrize("stem", sorted(CORPUS))
    def test_clean_twin_passes(self, stem):
        _, rule = CORPUS[stem]
        vs = self._lint_fixture(stem, "clean", rule)
        assert vs == [], "\\n".join(str(v) for v in vs)

    def test_corpus_is_complete(self):
        # >= 6 reconstructed review-round bugs, each with a clean twin
        assert len(CORPUS) >= 6
        for stem in CORPUS:
            for suffix in ("bug", "clean"):
                assert os.path.exists(
                    os.path.join(CORPUS_DIR, f"{stem}_{suffix}.py")), (
                    stem, suffix)


# ------------------------------------------------------- incremental mode


class TestChangedMode:
    def test_changed_lints_only_diffed_files(self, tmp_path):
        import subprocess as sp

        repo = tmp_path / "repo"
        (repo / "pilosa_tpu").mkdir(parents=True)
        (repo / "pilosa_tpu" / "clean.py").write_text("x = 1\n")
        env = dict(os.environ,
                   GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
                   GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t")
        for args in (["git", "init", "-q"], ["git", "add", "."],
                     ["git", "commit", "-qm", "seed"]):
            sp.run(args, cwd=repo, env=env, check=True, capture_output=True)
        # a tracked file grows a violation; an untracked bad file appears
        (repo / "pilosa_tpu" / "clean.py").write_text(
            "try:\n    work()\nexcept Exception:\n    pass\n")
        (repo / "pilosa_tpu" / "fresh.py").write_text(
            "try:\n    work()\nexcept Exception:\n    pass\n")
        proc = sp.run(
            [sys.executable, "-m", "tools.pilint", "--changed", "HEAD",
             "--root", str(repo)],
            cwd=repo, env=dict(env, PYTHONPATH=REPO_ROOT),
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "clean.py" in proc.stdout and "fresh.py" in proc.stdout
        assert proc.stdout.count("R1") == 2

    def test_changed_with_no_changes_exits_zero(self, tmp_path):
        import subprocess as sp

        repo = tmp_path / "repo"
        (repo / "pilosa_tpu").mkdir(parents=True)
        (repo / "pilosa_tpu" / "clean.py").write_text("x = 1\n")
        env = dict(os.environ,
                   GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
                   GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t")
        for args in (["git", "init", "-q"], ["git", "add", "."],
                     ["git", "commit", "-qm", "seed"]):
            sp.run(args, cwd=repo, env=env, check=True, capture_output=True)
        proc = sp.run(
            [sys.executable, "-m", "tools.pilint", "--changed", "HEAD",
             "--root", str(repo)],
            cwd=repo, env=dict(env, PYTHONPATH=REPO_ROOT),
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "0 violations" in proc.stdout

    def test_depth_flag_parsed(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.pilint", "--depth", "0",
             "pilosa_tpu/errors.py"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 2  # depth must be >= 1
