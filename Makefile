# Convenience wrappers around the repo's canonical commands (ROADMAP.md).
PY := PYTHONPATH=src python

.PHONY: test test-tier1 bench comm-table dryrun ci

test:            ## tier-1 verify: the full suite, fail fast
	$(PY) -m pytest -x -q

ci:              ## reproduce both .github/workflows/ci.yml jobs locally
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) -m pytest -x -q --junitxml=experiments/junit.xml
	$(PY) -m tools.test_durations experiments/junit.xml \
		experiments/slowest-tests.txt
	@test -z "$$(git status --porcelain)" || \
		{ git status --porcelain; \
		  echo "FAIL: tree dirty after tests (extend .gitignore)"; exit 1; }
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check src tests benchmarks tools; \
	else echo "ruff not installed locally; CI runs it"; fi
	python tools/lint_deprecated.py
	$(PY) -m benchmarks.run --smoke --json experiments/bench-smoke.json
	@$(PY) -c "import json; rows = json.load(open('experiments/bench-smoke.json')); \
		assert any('shard_update_plan' in r['name'] for r in rows), \
		'sharded smoke row missing from bench artifact'; \
		assert any('gather_ahead_plan' in r['name'] for r in rows), \
		'gather-ahead smoke row missing from bench artifact'; \
		assert any('zero3_plan' in r['name'] for r in rows), \
		'zero3 timeline smoke row missing from bench artifact'; \
		assert any('zero3_param_mem' in r['name'] for r in rows), \
		'zero3 peak-param-memory smoke row missing from bench artifact'; \
		assert any('zero3_param_mem_split' in r['name'] for r in rows), \
		'split-leaf zero3 memory smoke row missing from bench artifact'; \
		assert any('ckpt.roundtrip' in r['name'] for r in rows), \
		'ckpt-roundtrip smoke row missing from bench artifact'; \
		assert any('guard.overhead' in r['name'] for r in rows), \
		'guard sentinel-overhead smoke row missing from bench artifact'; \
		assert any('guard.recovery' in r['name'] for r in rows), \
		'guard recovery-ladder smoke row missing from bench artifact'"

test-tier1:      ## fast in-process subset (no 8-device subprocesses)
	$(PY) -m pytest -x -q -m "tier1 and not tier2"

bench:           ## paper-table benchmarks, quick variant
	$(PY) -m benchmarks.run --quick

comm-table:      ## predicted all-reduce time per schedule, production meshes
	$(PY) -m repro.launch.dryrun --comm-table

dryrun:          ## full multi-pod compile dry-run (slow)
	$(PY) -m repro.launch.dryrun
