#!/usr/bin/env bash
# Fast CI gate: byte-compile every tree we ship, run the fast test tier
# (pytest.ini defaults to -m "not slow"), then run three examples
# end-to-end: quickstart at PIR_SMOKE scale (the public serving facade —
# TwoServerPIR over the protocol registry), db_updates at PIR_SMOKE_UPD
# scale (the database plane's stage/publish path on the 3-server
# protocol), and single_server at PIR_SMOKE_LWE scale (the hint
# lifecycle on the 1-server LWE protocol), so API breakage in any plane
# is caught here instead of by users. The k-server facade demo
# (examples/multi_server.py) and the slow tier (system / sharding /
# compile-heavy) run out-of-band:  pytest -m slow
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

python -m compileall -q src examples scripts tests
python -m pytest -q
# smoke gate: one compiled serve step per party (~1 min each on the dev
# container), full client -> two servers -> reconstruct round trip
python examples/quickstart.py
# db-plane smoke: preload -> query -> stage+publish -> re-query on the
# 3-server protocol (tiny shape, one bucket: 3 serve compiles total)
python examples/db_updates.py
# single-server smoke: the LWE hint lifecycle end-to-end — query with
# hint reuse, publish -> hint delta + client cache refresh (cheap: the
# LWE GEMM has no GGM chains, its serve step compiles in ~1 s)
python examples/single_server.py
# replica-plane smoke: 2-replica fleet behind the router — publish
# fan-out converges epochs, a mid-load kill fails over with zero lost
# answers, and a warm rejoin serves its first query without re-tuning
# (PIR_SMOKE_REPL scale: 3 cheap LWE compiles total)
python examples/replicas.py
# batch-plane smoke: cuckoo-bucketed m=4 retrieval at PIR_SMOKE_BATCH
# scale — uniform B-wide rounds, a mid-session stage+publish landing in
# every candidate bucket, checksummed reconstruction, and the one-compile-
# per-party invariant (B buckets share one serve step: 2 compiles total)
python examples/batch_query.py
# engine-plane smoke: tiny-budget autotune (interpret mode, <=2 candidates
# per kernel, nothing persisted) + the heuristic-fallback gate — asserts
# an empty plan cache resolves to exactly the heuristic's pinned choices
python -m repro.engine --smoke
# chaos-plane smoke: seeded kill + share-corruption scenarios on the
# 2-replica LWE fleet — asserts detection (InjectedFault / IntegrityError,
# never a silently wrong record) AND recovery (every answer byte-correct
# on the survivor after failover; 4 cheap LWE compiles total)
python -m repro.chaos --smoke
