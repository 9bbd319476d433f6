"""MEP-Opt core: the paper's contribution as a composable module.

Pipeline:  extract (KernelCase) → complete (build_mep, eq. 1–2) →
iterate (optimize, eq. 3–5, AER, PPI) → reintegrate (integrate.install).
"""
from repro.core.kernelcase import (ArraySpec, KernelCase, Variant, cases,
                                   get_case, register)
from repro.core.datagen import DataBudget, generate
from repro.core.measure import (MeasureConfig, TimingLease, get_lease,
                                measure_callable, measure_fn,
                                trimmed_stats)
from repro.core.mep import MEP, MEPConstraints, build_mep, emit_script
from repro.core.profiler import (CPUPlatform, Platform, TimingResult,
                                 TPUModelPlatform, platform_from_name,
                                 register_platform, trimmed_mean, wallclock)
from repro.core.fe import FEResult, check as fe_check, outputs_match
from repro.core.aer import AER, RepairRecord, WorkerFault
from repro.core.patterns import Pattern, PatternStore
from repro.core.proposer import (DirectProposer, HeuristicProposer,
                                 LLMBatcher, LLMProposer, OfflineError,
                                 PERSONAE, Proposer, RoundState,
                                 make_proposer, persona_proposers,
                                 proposer_from_spec)
from repro.core.population import (Individual, Population,
                                   PopulationConfig)
from repro.core.evalcache import (EvalCache, EvalRecord, ResultsDB,
                                  canonical_spec, default_namespace,
                                  spec_key, this_host)
from repro.core.optimizer import (CandidateLog, Evaluator, OptConfig,
                                  OptResult, RoundLog, optimize)
from repro.core.workers import (CaseJob, Executor, InProcessExecutor,
                                LocalClusterExecutor, SubprocessExecutor,
                                WorkerContext, make_executor, run_case_job)
from repro.core.campaign import Campaign
from repro.core import integrate
from repro.core import extraction
