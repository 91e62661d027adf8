"""Dask wrapper tests (reference model: tests/python_package_test/test_dask.py).

dask is not bundled in this image, so the orchestration logic is exercised
with lightweight fakes implementing the small client/collection surface the
wrapper uses; real-dask tests run when dask.distributed is installed.
"""

import numpy as np
import pytest

import lightgbm_tpu.dask as lgb_dask
from lightgbm_tpu.dask import (DASK_INSTALLED, DaskLGBMClassifier,
                               DaskLGBMRegressor, _concat_parts)


def test_import_without_dask_and_clear_error():
    est = DaskLGBMRegressor(n_estimators=5)
    if not DASK_INSTALLED:
        with pytest.raises(ImportError, match="dask"):
            est.fit(object(), object())


def test_concat_parts():
    a = np.arange(6).reshape(3, 2)
    b = np.arange(6, 12).reshape(3, 2)
    out = _concat_parts([a, b])
    assert out.shape == (6, 2)
    v = _concat_parts([np.arange(3), np.arange(3, 5)])
    np.testing.assert_array_equal(v, np.arange(5))


class _FakeFuture:
    def __init__(self, value, key, worker):
        self._v = value
        self.key = key
        self.worker = worker

    def result(self):
        return self._v


class _FakeClient:
    def __init__(self, nparts):
        self.nparts = nparts

    def compute(self, parts):
        return [_FakeFuture(p._value, f"k{i}", f"w{i % 2}")
                for i, p in enumerate(parts)]

    def who_has(self, futures):
        return {f.key: (f.worker,) for f in futures}

    def scheduler_info(self):
        return {"workers": {"w0": {}, "w1": {}}}


class _FakeDelayed:
    def __init__(self, value):
        self._value = value


class _FakeArray:
    """Duck-types the slice of the dask.array API the wrapper touches."""

    def __init__(self, arr, nparts=4):
        self._arr = np.asarray(arr)
        self.dask = {}
        self.ndim = self._arr.ndim
        self._parts = np.array_split(self._arr, nparts, axis=0)

    def to_delayed(self):
        return np.asarray([_FakeDelayed(p) for p in self._parts],
                          dtype=object)

    def compute(self):
        return self._arr

    def map_blocks(self, fn, **_kwargs):
        return np.concatenate([np.asarray(fn(p)).reshape(-1)
                               for p in self._parts])


@pytest.fixture
def fake_dask(monkeypatch):
    monkeypatch.setattr(lgb_dask, "DASK_INSTALLED", True)
    monkeypatch.setattr(lgb_dask, "default_client", lambda: _FakeClient(4))
    monkeypatch.setattr(lgb_dask, "wait", lambda futures: None)


def _make_data(n=600, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, 5))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.int32)
    return X, y


def test_fake_dask_classifier_roundtrip(fake_dask):
    X, y = _make_data()
    dX, dy = _FakeArray(X), _FakeArray(y)
    est = DaskLGBMClassifier(n_estimators=10, num_leaves=15, verbosity=-1)
    est.fit(dX, dy, client=_FakeClient(4), distributed=False)
    pred = est.predict(_FakeArray(X))
    assert pred.shape == (len(y),)
    assert np.mean(pred == y) > 0.9
    # to_local returns a plain estimator that predicts identically
    local = est.to_local()
    np.testing.assert_allclose(local.predict(X), pred)


def test_fake_dask_regressor(fake_dask):
    X, y = _make_data()
    yr = X[:, 0] * 2.0 + X[:, 2]
    est = DaskLGBMRegressor(n_estimators=15, num_leaves=15, verbosity=-1)
    est.fit(_FakeArray(X), _FakeArray(yr), client=_FakeClient(4),
            distributed=False)
    pred = est.predict(_FakeArray(X))
    assert np.mean((pred - yr) ** 2) < 0.3 * np.var(yr)


import os as _os

_REPO_ROOT = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))


class _FakeDistClient(_FakeClient):
    """Fake client whose submit() runs `_train_part` ranks in REAL
    subprocesses (each becomes a jax.distributed process), so the
    per-worker data plane is exercised end-to-end without dask: the
    client process never touches partition contents.  Worker names are
    real host:port addresses so the coordinator derivation works."""

    WORKERS = ("tcp://127.0.0.1:40101", "tcp://127.0.0.1:40102")

    def __init__(self, nparts, tmp_path):
        super().__init__(nparts)
        self.tmp = tmp_path

    def compute(self, parts):
        return [_FakeFuture(p._value, f"k{i}",
                            self.WORKERS[i % len(self.WORKERS)])
                for i, p in enumerate(parts)]

    def scheduler_info(self):
        return {"workers": {w: {} for w in self.WORKERS}}

    def submit(self, fn, *args, workers=None, allow_other_workers=None,
               pure=None, **kw):
        import lightgbm_tpu.dask as mod
        if fn is not mod._train_part:
            # small helper submissions (per-part uniques) run inline
            val = fn(*[a.result() if isinstance(a, _FakeFuture) else a
                       for a in args])
            return _FakeFuture(val, f"inline-{id(val)}", None)
        import pickle
        import subprocess
        import sys

        def resolve(a):
            if isinstance(a, list):
                return [x.result() if isinstance(x, _FakeFuture) else x
                        for x in a]
            return a.result() if isinstance(a, _FakeFuture) else a

        rank = args[7]
        argfile = self.tmp / f"rank{rank}.pkl"
        outfile = self.tmp / f"rank{rank}.out.pkl"
        with open(argfile, "wb") as f:
            pickle.dump([resolve(a) for a in args], f)
        code = (
            "import os, pickle, sys\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "os.environ.pop('XLA_FLAGS', None)\n"
            "os.environ['JAX_COMPILATION_CACHE_DIR'] = "
            f"{_os.path.join(_REPO_ROOT, '.jax_cache', 'tests-multiprocess')!r}\n"
            "import jax\n"
            f"args = pickle.load(open({str(argfile)!r}, 'rb'))\n"
            # initialize BEFORE the package import can touch the backend
            "jax.distributed.initialize(coordinator_address=args[9],\n"
            "    num_processes=args[8], process_id=args[7])\n"
            f"sys.path.insert(0, {_REPO_ROOT!r})\n"
            "from lightgbm_tpu.dask import _train_part\n"
            "out = _train_part(*args)\n"
            f"pickle.dump(out, open({str(outfile)!r}, 'wb'))\n")
        p = subprocess.Popen([sys.executable, "-c", code],
                             stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT)
        fut = _FakeFuture(None, f"train{rank}", workers[0])
        fut._proc, fut._outfile = p, outfile
        return fut

    def gather(self, futures):
        import pickle
        out = []
        for f in futures:
            if getattr(f, "_proc", None) is not None:
                log = f._proc.communicate(timeout=900)[0].decode()
                assert f._proc.returncode == 0, log[-3000:]
                out.append(pickle.load(open(f._outfile, "rb")))
            else:
                out.append(f.result())
        return out


def test_fake_dask_distributed_per_worker_plane(fake_dask, tmp_path):
    """The distributed fit path: partitions stay on their workers, each
    worker trains as a jax.distributed rank (a real 2-process run via
    the subprocess-backed fake), and the client only ever receives the
    model text."""
    X, y = _make_data(n=1200)
    port = 12600 + _os.getpid() % 300
    est = DaskLGBMClassifier(n_estimators=10, num_leaves=15, verbosity=-1,
                             min_child_samples=5, local_listen_port=port)
    client = _FakeDistClient(4, tmp_path)
    est.fit(_FakeArray(X), _FakeArray(y), client=client)
    assert est._Booster is not None
    pred = est.predict(_FakeArray(X))
    assert np.mean(pred == y) > 0.9


@pytest.mark.skipif(not DASK_INSTALLED, reason="dask not installed")
def test_real_dask_roundtrip():
    import dask.array as da
    from distributed import Client, LocalCluster
    X, y = _make_data()
    with LocalCluster(n_workers=2, threads_per_worker=1,
                      processes=False) as cluster, Client(cluster) as client:
        dX = da.from_array(X, chunks=(150, 5))
        dy = da.from_array(y, chunks=(150,))
        est = DaskLGBMClassifier(n_estimators=10, num_leaves=15,
                                 verbosity=-1)
        est.fit(dX, dy, client=client)
        pred = np.asarray(est.predict(dX))
        assert np.mean(pred == y) > 0.9


@pytest.mark.slow
@pytest.mark.skipif(not DASK_INSTALLED, reason="dask not installed")
def test_real_dask_distributed_two_workers_matches_gather():
    """distributed=True on a REAL 2-process LocalCluster: each dask
    worker becomes a jax.distributed rank over its resident partitions
    (the per-worker plane the fake-client test drives via
    subprocesses), and the result must match the gather-to-client
    path's model — data-parallel histograms change only f32 summation
    order, so predictions agree to float noise.  Slow: spawns worker
    processes and a jax.distributed coordinator."""
    import dask.array as da
    from distributed import Client, LocalCluster
    X, y = _make_data(n=1200)
    with LocalCluster(n_workers=2, threads_per_worker=1, processes=True,
                      dashboard_address=None) as cluster, \
            Client(cluster) as client:
        dX = da.from_array(X, chunks=(300, X.shape[1]))
        dy = da.from_array(y, chunks=(300,))
        kw = dict(n_estimators=8, num_leaves=15, verbosity=-1,
                  min_child_samples=5)
        dist = DaskLGBMClassifier(**kw).fit(dX, dy, client=client,
                                            distributed=True)
        gath = DaskLGBMClassifier(**kw).fit(dX, dy, client=client,
                                            distributed=False)
        pd_dist = np.asarray(dist.predict(dX, raw_score=True))
        pd_gath = np.asarray(gath.predict(dX, raw_score=True))
        np.testing.assert_allclose(pd_dist, pd_gath, rtol=1e-3,
                                   atol=1e-4)
        pred = np.asarray(dist.predict(dX))
        assert np.mean(pred == y) > 0.9
