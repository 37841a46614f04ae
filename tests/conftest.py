"""Shared fixtures."""
import pytest


@pytest.fixture
def plan_cache_off(monkeypatch):
    """The process-wide plan cache, empty and in memory for one test:
    plans the test records with ``engine.record_plans`` go with it."""
    from repro import engine
    monkeypatch.setenv("REPRO_PLAN_CACHE", "off")
    engine.plan_cache(reload=True)
    yield
    monkeypatch.delenv("REPRO_PLAN_CACHE")
    engine.plan_cache(reload=True)
