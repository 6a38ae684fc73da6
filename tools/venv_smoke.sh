#!/usr/bin/env bash
# Package smoke test WITHOUT docker: prove `pip install -e .` in a clean
# virtualenv yields working console entry points — the no-docker analog of
# docker/smoke.sh (round-4 verdict: until *something* executes, the package
# layer is plausible rather than proven; this is the something for hosts
# without a docker daemon, like the air-gapped box this repo is built on).
#
#   ./tools/venv_smoke.sh [workdir]     # default: a fresh mktemp -d
#
# What it checks, in order:
#   1. `python -m venv` + `pip install -e . --no-deps --no-build-isolation`
#      succeed (pyproject metadata parses, the package installs, console
#      scripts materialize). --no-deps + a .pth exposing the host image's
#      site-packages: jax/flax/optax/orbax come from the host — this box has
#      zero egress, and the deps contract is pyproject's; what's under test
#      here is the PACKAGING, not the resolver. (A .pth, not
#      --system-site-packages: the host python is itself a venv, and
#      venv-from-venv resolves "system" to the BASE CPython, which has
#      nothing.)
#   2. `dmt-hello-world --platform cpu --n_virtual_devices 4` exits 0 and
#      prints broadcast/ring/psum OK — collectives on a 4-device mesh through
#      the installed entry point (not the repo checkout: we cd out of it).
#   3. `dmt-train-lm` runs one tiny epoch end to end — trainer, loader,
#      checkpoint, and log plumbing all import from the installed package.
#
# The passing transcript is committed under docs/runs/venv_smoke/.

set -euo pipefail
REPO="$(cd "$(dirname "$0")/.." && pwd)"
WORK="${1:-$(mktemp -d)}"
VENV="$WORK/venv"

echo "--- venv + editable install ---"
python -m venv "$VENV"
HOST_SITE="$(python -c 'import sysconfig; print(sysconfig.get_paths()["purelib"])')"
VENV_SITE="$("$VENV/bin/python" -c 'import sysconfig; print(sysconfig.get_paths()["purelib"])')"
echo "$HOST_SITE" > "$VENV_SITE/_host_deps.pth"
"$VENV/bin/pip" install -e "$REPO" --no-deps --no-build-isolation --quiet
# No `| head` here: head's early close SIGPIPEs pip under pipefail.
"$VENV/bin/pip" show deeplearning-mpi-tpu > "$WORK/pip_show.txt"
sed -n 1,2p "$WORK/pip_show.txt"

# Run from OUTSIDE the repo so imports resolve through the installed
# package, not the checkout's CWD.
cd "$WORK"

echo "--- dmt-hello-world (4 virtual CPU devices) ---"
"$VENV/bin/dmt-hello-world" --platform cpu --n_virtual_devices 4

echo "--- dmt-train-lm (one tiny epoch) ---"
"$VENV/bin/dmt-train-lm" --platform cpu --n_virtual_devices 4 \
    --num_epochs 1 --batch_size 8 --seq_len 32 --num_layers 1 \
    --num_heads 2 --head_dim 8 --d_model 16 --d_ff 32 \
    --train_sequences 16 --eval_every 1 \
    --model_dir "$WORK/ckpt" --log_dir "$WORK/logs"

test -d "$WORK/ckpt/lm" || { echo "no checkpoint written" >&2; exit 1; }
echo "venv_smoke OK: install + hello_world + train-lm epoch + checkpoint"
