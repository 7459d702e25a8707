# Developer entry points. `make lint` is what CI runs; see
# docs/development.md for the lint rules and suppression syntax.

PYTHON ?= python

.PHONY: lint lint-graph lint-fixtures test

# Whole-program by default: one parse per file feeds the file-local
# families, the project graph, and the cross-file passes alike.
lint:
	$(PYTHON) -m hypha_tpu.analysis hypha_tpu/
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check hypha_tpu/ tests/ --exclude tests/fixtures; \
	else \
		echo "ruff not installed; skipping (hypha-lint ran above)"; \
	fi

# Dump the call/handler graph the whole-program passes walk (debugging
# aid: "why is there no edge" is answered by the external_calls lines).
lint-graph:
	$(PYTHON) -m hypha_tpu.analysis --dump-graph hypha_tpu/

# The seeded-violation fixtures must FAIL the linter — run as a sanity
# check that the rules still fire (tests/test_lint.py asserts per-rule).
lint-fixtures:
	@for f in tests/fixtures/lint/async_bad.py \
		tests/fixtures/lint/conformance_pkg \
		tests/fixtures/lint/guard_pkg \
		tests/fixtures/lint/flow_pkg \
		tests/fixtures/lint/leak_pkg; do \
		if $(PYTHON) -m hypha_tpu.analysis --no-proto $$f >/dev/null; then \
			echo "ERROR: $$f passed the linter"; exit 1; \
		else \
			echo "$$f correctly rejected"; \
		fi; \
	done

test:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider
