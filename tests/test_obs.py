"""The program's spans and named scopes (``repro.obs``): one
``snapstore.fleet.read`` span per host call with its counts, the read
program still compiled as ``jit_read`` with its ``fleet.resolve`` and
``fleet.gather`` scopes in the op metadata, and the serving and
maintenance spans, each checked on a short CPU trace."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.configs import smoke_config
from repro.core import fleet as fleet_lib
from repro.core.scheduler import MaintenanceScheduler
from repro.models import get_model
from repro.serve.engine import Engine

KEY = jax.random.PRNGKey(0)


def record(tmp_path, fn):
    """Run ``fn`` under a profiler trace; return its ``snapstore.*`` host
    spans as ``(name, start_ns, end_ns, args)``, by start."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(obs.PREFIX):
                    out.append((ev.name[len(obs.PREFIX):], int(ev.start_ns),
                                int(ev.end_ns), {k: v for k, v in ev.stats}))
    return sorted(out, key=lambda s: s[1])


def small_fleet(n_pages):
    spec = fleet_lib.FleetSpec(n_tenants=2, n_pages=n_pages, page_size=128,
                               max_chain=4, pool_capacity=256,
                               lease_quantum=16, l2_per_table=64,
                               slice_len=16)
    fl = fleet_lib.create(spec, scalable=jnp.asarray([True, False]))
    ids = jnp.tile(jnp.arange(8, dtype=jnp.int32)[None], (2, 1))
    fl = fleet_lib.write(fl, ids, jnp.ones((2, 8, 128)))
    return fleet_lib.snapshot(fl), ids


def test_a_span_records_nothing_without_a_trace():
    with obs.span("idle", n=1) as ann:
        assert not ann.is_enabled()
        ann.set_metadata(m=2)


# a kernel method resolves each tenant's whole table, a jnp method only
# the batch: (method, n_pages, entries resolved per tenant)
@pytest.mark.parametrize("method,n_pages,per_tenant", [
    ("auto", 128, 128),          # lane-aligned: the Pallas kernel path
    ("pallas_vanilla", 192, 192),
    ("vanilla", 128, 8),         # the vmapped jnp walk
    ("auto", 192, 8),            # not lane-aligned: jnp auto
])
def test_fleet_read_opens_one_span_per_call(tmp_path, method, n_pages,
                                            per_tenant):
    fl, ids = small_fleet(n_pages)
    jax.block_until_ready(fleet_lib.read(fl, ids, method=method))

    def reads():
        for _ in range(3):
            jax.block_until_ready(fleet_lib.read(fl, ids, method=method))

    spans = record(tmp_path, reads)
    assert [s[0] for s in spans] == ["fleet.read"] * 3
    for _, _, _, args in spans:
        assert args == dict(pages=16, resolved=2 * per_tenant, method=method)


def test_read_lowers_as_jit_read_with_its_scopes():
    fl, ids = small_fleet(128)
    text = fleet_lib.read.lower(fl, ids, method="auto").as_text()
    assert re.search(r"module @jit_read\b", text)
    hlo = fleet_lib.read.lower(fl, ids, method="auto").compile().as_text()
    assert hlo.startswith("HloModule jit_read,")
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    assert any(n.startswith("jit(read)/fleet.resolve/") for n in names)
    assert any(n.startswith("jit(read)/fleet.gather/") for n in names)
    assert any("resolve_vanilla_fleet_pallas" in n and "fleet.resolve" in n
               for n in names)


def test_read_traced_inside_another_jit_spans_only_while_tracing(tmp_path):
    fl, ids = small_fleet(128)
    total = jax.jit(lambda f, i: fleet_lib.read(f, i, method="vanilla")[0]
                    .sum())
    want = float(fleet_lib.read(fl, ids, method="vanilla")[0].sum())
    first = record(tmp_path / "a", lambda: total(fl, ids).block_until_ready())
    assert [s[0] for s in first] == ["fleet.read"]
    again = record(tmp_path / "b", lambda: total(fl, ids).block_until_ready())
    assert again == []
    assert float(total(fl, ids)) == want == 8 * 128 * 2


def test_engine_spans_carry_sid_path_and_rows(tmp_path):
    cfg = smoke_config("qwen2-7b")
    eng = Engine(cfg, get_model(cfg).init(KEY), scalable=True, n_blocks=64,
                 block_size=4, max_blocks_per_seq=16)
    prompt = np.asarray(jax.random.randint(KEY, (9,), 0, cfg.vocab_size))
    eng.register_golden(prompt)
    longer = np.concatenate([prompt, prompt[:3]])
    other = (prompt + 1) % cfg.vocab_size
    sids = {}

    def serve():
        sids["golden"] = eng.add_request(longer)
        sids["full"] = eng.add_request(other)
        eng.step()

    spans = record(tmp_path, serve)
    admits = [s for s in spans if s[0] == "engine.admit"]
    assert [a[3] for a in admits] == [
        dict(tokens=12, sid=sids["golden"], path="golden"),
        dict(tokens=9, sid=sids["full"], path="full")]
    (step,) = [s for s in spans if s[0] == "engine.step"]
    assert step[3] == dict(rows=2, padded_rows=2)
    inner = [s for s in spans if step[1] <= s[1] and s[2] <= step[2]
             and s is not step]
    assert [s[0] for s in inner] == ["kv.prepare", "kv.commit",
                                     "engine.sample"]
    assert inner[0][3] == dict(rows=2) == inner[2][3]


def test_maintenance_tick_span_carries_its_report(tmp_path):
    fl, _ = small_fleet(128)
    sched = MaintenanceScheduler(fl, max_tenants_per_tick=1)
    reports = []
    spans = record(tmp_path, lambda: reports.append(sched.tick()))
    (tick,) = spans
    rep = reports[0]
    assert tick[0] == "maintenance.tick"
    assert tick[3] == dict(streamed=len(rep["streamed"]),
                           compacted=int(rep["compacted"]),
                           rows_demoted=rep["rows_demoted"],
                           backlog=rep["backlog"])
