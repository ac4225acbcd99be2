# Development makefile (ref makefile:1 — its desktop dev commands; these
# target the TPU framework's actual workflows).
.PHONY: help install test analyze lint dryrun serve docker

PY ?= python

help: ## Show available commands
	@grep -E '^[a-zA-Z_-]+:.*?## .*$$' $(MAKEFILE_LIST) | sort | \
	  awk 'BEGIN {FS = ":.*?## "}; {printf "%-12s %s\n", $$1, $$2}'

install: ## Editable install with the lumina console script
	pip install -e .[dev]

test: ## The suite as the driver runs it (CPU, 8 virtual devices, six workers)
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m "not slow" -p xdist -n 6 --dist loadfile

analyze: ## Static-analysis gate (astlint rules + abstract-eval audits)
	JAX_PLATFORMS=cpu lumina analyze

lint: ## Sub-second lint-only loop (no jax tracing); + ruff if installed
	lumina analyze --no-audit
	@if command -v ruff >/dev/null 2>&1; then ruff check .; else echo "ruff not installed; skipping (CI runs it)"; fi

dryrun: ## 8-device multichip sharding dry run (virtual CPU mesh)
	$(PY) __graft_entry__.py 8

serve: ## Serve the latest checkpoint found under . (API + chat UI at /)
	lumina serve

docker: ## Build the serving image
	docker build -t lumina-tpu .
