"""Perl binding tests (perl-package/AI-MXNetTPU; parity: reference
perl-package/AI-MXNet, minimal training-capable surface).

Builds the XS extension with ExtUtils::MakeMaker against the general C
ABI and runs examples/train_linreg.pl in a fresh perl process: NDArray
round-trip, imperative ops, autograd record/backward, sgd_update — a
non-C language training end-to-end through src/c_api.h.
"""
import os
import shutil
import subprocess

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG = os.path.join(_REPO, "perl-package", "AI-MXNetTPU")
_LIB = os.path.join(_REPO, "src", "build", "libmxnet_tpu_c.so")


def _ready():
    if shutil.which("perl") is None:
        return False
    if not os.path.exists(_LIB):
        try:
            subprocess.run(["make", "-C", os.path.join(_REPO, "src"),
                            "capi"], check=True, capture_output=True,
                           timeout=180)
        except Exception:
            return False
    so = os.path.join(_PKG, "blib", "arch", "auto", "AI", "MXNetTPU",
                      "MXNetTPU.so")
    if os.path.exists(so):
        return True
    try:
        subprocess.run(["perl", "Makefile.PL"], cwd=_PKG, check=True,
                       capture_output=True, timeout=120)
        subprocess.run(["make"], cwd=_PKG, check=True,
                       capture_output=True, timeout=300)
        return os.path.exists(so)
    except Exception:
        return False


needs_perl = pytest.mark.skipif(not _ready(),
                                reason="perl/XS build unavailable")


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


@needs_perl
def test_perl_ndarray_and_ops():
    r = subprocess.run(
        ["perl", "-Mblib", "-MAI::MXNetTPU", "-e", """
my $x = AI::MXNetTPU::NDArray->new([2,2], [1,2,3,4]);
my ($y) = AI::MXNetTPU::invoke('elemwise_add', [$x, $x]);
my @v = $y->to_list;
die "bad: @v" unless "@v" eq "2 4 6 8";
my @ops = AI::MXNetTPU::list_ops();
die "too few ops" unless @ops > 300;
print "PERL-OPS-OK\\n";
"""], cwd=_PKG, capture_output=True, text=True, timeout=300, env=_env())
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "PERL-OPS-OK" in r.stdout


@needs_perl
def test_perl_training_converges():
    r = subprocess.run(
        ["perl", os.path.join(_PKG, "examples", "train_linreg.pl")],
        cwd=_PKG, capture_output=True, text=True, timeout=300, env=_env())
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "PASS" in r.stdout
