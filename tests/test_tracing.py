"""The fit's own names in a profiler trace: the host spans of
``EnforcedNMF.fit`` and the named scopes of the ALS engine's loop body.

Host spans (``jax.profiler.TraceAnnotation``) land on the profiler's host
clock; named scopes (``jax.named_scope``) land in each device op's
``op_name`` metadata, which the compiled HLO shows."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from repro.backend import get_backend
from repro.core.nmf import als_nmf
from repro.nmf import EnforcedNMF, NMFConfig, Sparsity

from _hlo import ENGINE_SCOPES, SCOPE, loop_body, op_name

#: every host span a one-chunk fit writes, once each
FIT_SPANS = ("nmf.fit", "nmf.prepare", "nmf.dispatch", "nmf.sync",
             "nmf.result", "nmf.seed_stats")
N, M, K = 256, 192, 4


@pytest.fixture(scope="module")
def operand():
    a = sp.random(N, M, density=0.05, random_state=0, format="csr",
                  dtype=np.float32)
    return get_backend("pallas-bsr").prepare(a, dtype=np.float32)


def _config(**kw):
    kw = {"iters": 3, "tol": 0.0, **kw}
    return NMFConfig(k=K, sparsity=Sparsity(t_u=100, t_v=80),
                     solver="enforced", backend="pallas-bsr", **kw)


def _u0():
    return jnp.asarray(np.random.default_rng(0).random((N, K), np.float32))


def _host_events(trace_dir):
    """``(name, start_ns, end_ns)`` of every event on the host's threads."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    assert path, f"no trace written under {trace_dir}"
    out = []
    for plane in ProfileData.from_file(path[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns) for e in line.events]
    return out


def test_a_traced_fit_writes_each_span_once_inside_nmf_fit(operand,
                                                           tmp_path):
    model = EnforcedNMF(_config())
    u0 = _u0()
    model.fit(operand, u0=u0)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        model.fit(operand, u0=u0)
        jax.block_until_ready(model.u_)
    spans = [e for e in _host_events(tmp_path) if e[0].startswith("nmf.")]
    names = [name for name, _, _ in spans]
    assert sorted(names) == sorted(FIT_SPANS), names
    (_, fit_a, fit_b), = [e for e in spans if e[0] == "nmf.fit"]
    for name, a, b in spans:
        assert fit_a <= a <= b <= fit_b, name
    start = {name: a for name, a, _ in spans}
    order = sorted(FIT_SPANS[1:], key=start.get)
    assert order == ["nmf.prepare", "nmf.dispatch", "nmf.sync",
                     "nmf.result", "nmf.seed_stats"]


def test_the_driver_names_every_device_read(operand, tmp_path):
    """With ``tol`` the residual read is a blocking read too: one
    ``nmf.sync`` for the health and one for the residual of each chunk."""
    model = EnforcedNMF(_config(tol=1e-30, iters=12))
    u0 = _u0()
    model.fit(operand, u0=u0)
    with jax.profiler.trace(str(tmp_path)):
        model.fit(operand, u0=u0)
    names = [e[0] for e in _host_events(tmp_path)
             if e[0].startswith("nmf.")]
    chunks = 2  # 12 iterations in chunks of 10
    assert names.count("nmf.dispatch") == chunks
    assert names.count("nmf.sync") == 2 * chunks
    assert names.count("nmf.result") == names.count("nmf.fit") == 1


def test_the_compiled_engine_carries_every_scope(operand):
    cfg = _config()
    sp_u = cfg.sparsity.sparsifier(N, K, "u", fused=True)
    sp_v = cfg.sparsity.sparsifier(M, K, "v", fused=True)
    text = als_nmf.lower(operand, _u0(), iters=3,
                         sparsify_u=sp_u, sparsify_v=sp_v, track_error=True,
                         backend="pallas-bsr").compile().as_text()
    found = {m.group(1) for name in re.findall(r'op_name="([^"]*)"', text)
             for m in [SCOPE.search(name)] if m}
    assert found == set(ENGINE_SCOPES)
    # every fusion or custom call the body function made sits in a scope;
    # the scan's own counter and output stacking lie outside the body call
    # (``.../while/body/<op>``), and ops the compiler makes carry no
    # ``op_name`` or only the call's own
    unscoped = []
    for line in loop_body(text):
        if not re.search(r"\s(fusion|custom-call)\(", line):
            continue
        name = op_name(line)
        if "/while/body/closed_call/" in name and not SCOPE.search(name):
            unscoped.append(line.strip()[:160])
    assert not unscoped, unscoped
