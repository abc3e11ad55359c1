#!/usr/bin/env bash
# Tier-1 verification (see ROADMAP.md "Tier-1 verify"): the repo's own
# test suite. Extra arguments go to pytest.
#
#   bash scripts/verify.sh [pytest args]
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

python -m pytest -x -q "$@"
