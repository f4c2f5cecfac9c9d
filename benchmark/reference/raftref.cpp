// Plain reference checker for the benchmark's Raft configurations: an
// explicit-state BFS of Raft.tla under Raft.cfg semantics (VIEW dedup,
// SYMMETRY canonicalization, INVARIANT Inv), independent of the checker
// under test.  It imports nothing of it and shares no code with it.
//
// Semantics (cited against Raft.tla / Raft.cfg):
//   * the 11 live Next disjuncts (Raft.tla:416-430),
//   * VIEW = the 8 real vars (Raft.tla:38), aux excluded,
//   * SYMMETRY symmServers (Raft.cfg): canonical fingerprint is the
//     min over all S! server permutations of a 64-bit multilinear hash
//     of the permuted view (set-sum over messages, so no per-perm sort),
//   * Inv = LeaderHasAllCommittedEntries (Raft.tla:491-499, the spec's
//     exists-a-good-leader form) checked on every distinct state,
//   * the in-path split-brain Assert (Raft.tla:185),
//   * deadlock not reported.
//
// Exploration is level-synchronous BFS, parallelized across worker
// threads per level.  Counts are deterministic and thread-count
// independent: within a level, duplicate view fingerprints collapse to
// the min-(canonical-full-encoding) representative.  A violating run
// finishes interning its level, then reports.
//
// Two options exist only for the benchmark's control, which must come
// out as not correct: --fp-bits N keeps N bits of a salted canonical
// fingerprint (64 = exact), and --fold stab1 folds over the permutations
// that fix s1 only (a broken SYMMETRY reduction).
//
// Build: g++ -O3 -std=c++17 -pthread raftref.cpp -o raftref
// Run:   ./raftref S V maxElection maxRestart maxDepth threads
//                  [--fp-bits N] [--salt X] [--fold full|stab1]
// Emits one JSON line: verdict, per-level new states and the cumulative
// generated count after each expanded level.

#include <algorithm>
#include <atomic>
#include <chrono>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <string>
#include <thread>
#include <vector>

namespace {

// ---- bounds (compile-time caps; runtime config must fit) -------------
constexpr int MAXS = 7;   // servers
constexpr int MAXL = 8;   // log entries incl. the (0,0) sentinel
constexpr int MAXM = 127; // messages per reachable state

constexpr uint8_t FOLLOWER = 0, CANDIDATE = 1, LEADER = 2;
constexpr uint8_t VOTE_REQ = 0, VOTE_RESP = 1, APPEND_REQ = 2,
                  APPEND_RESP = 3;

struct Cfg {
  int S = 3, V = 2, maxE = 3, maxR = 3;
  // control options (see the header): 64 bits and the full fold are exact
  int fp_bits = 64;
  uint64_t salt = 0;
  bool fold_stab1 = false;
  int majority() const { return S / 2 + 1; }
};

// ---- message packing (one u32 per message) ---------------------------
// type:2 | src:3 | dst:3 | term:4 | f4:4 | f5:4 | has_entry:1 |
// eterm:4 | eval:3  (f4 = lastLogIndex/prevLogIndex, f5 =
// lastLogTerm/prevLogTerm/succ; leaderCommit rides in bits 28..31)
struct Msg {
  static uint32_t pack(uint8_t type, uint8_t src, uint8_t dst, uint8_t term,
                       uint8_t f4 = 0, uint8_t f5 = 0, bool has_e = false,
                       uint8_t eterm = 0, uint8_t eval = 0, uint8_t lc = 0) {
    return uint32_t(type) | uint32_t(src) << 2 | uint32_t(dst) << 5 |
           uint32_t(term) << 8 | uint32_t(f4) << 12 | uint32_t(f5) << 16 |
           uint32_t(has_e) << 20 | uint32_t(eterm) << 21 |
           uint32_t(eval) << 25 | uint32_t(lc) << 28;
  }
  static uint8_t type(uint32_t m) { return m & 3; }
  static uint8_t src(uint32_t m) { return (m >> 2) & 7; }
  static uint8_t dst(uint32_t m) { return (m >> 5) & 7; }
  static uint8_t term(uint32_t m) { return (m >> 8) & 15; }
  static uint8_t f4(uint32_t m) { return (m >> 12) & 15; }
  static uint8_t f5(uint32_t m) { return (m >> 16) & 15; }
  static bool has_e(uint32_t m) { return (m >> 20) & 1; }
  static uint8_t eterm(uint32_t m) { return (m >> 21) & 15; }
  static uint8_t eval(uint32_t m) { return (m >> 25) & 7; }
  static uint8_t lc(uint32_t m) { return (m >> 28) & 15; }
  // apply a server permutation p (1-based images) to src/dst
  static uint32_t permute(uint32_t m, const uint8_t *p) {
    uint32_t keep = m & ~uint32_t((7 << 2) | (7 << 5));
    return keep | uint32_t(p[src(m) - 1]) << 2 | uint32_t(p[dst(m) - 1]) << 5;
  }
};

// ---- state (12 variables, oracle/explicit.py OState) ------------------
struct State {
  uint8_t voted_for[MAXS];       // 0 = None
  uint8_t current_term[MAXS];
  uint8_t role[MAXS];
  uint8_t log_term[MAXS][MAXL];  // [s][i] = logs[s][i+1].term (TLA 1-based)
  uint8_t log_val[MAXS][MAXL];
  uint8_t log_len[MAXS];         // = Len(logs[s]), >= 1 (sentinel)
  uint8_t match_index[MAXS][MAXS];
  uint8_t next_index[MAXS][MAXS];
  uint8_t commit_index[MAXS];
  uint8_t election_count, restart_count;
  uint8_t pending[MAXS];         // bitmask over dst (S <= 8)
  uint8_t val_sent;              // bitmask over vals (V <= 8); 1 = FALSE
  uint8_t n_msgs;
  uint32_t msgs[MAXM];           // ascending, unique

  bool has_msg(uint32_t m) const {
    return std::binary_search(msgs, msgs + n_msgs, m);
  }
  // set-union insert; aborts loudly on overflow — a silently dropped
  // message would make the baseline explore a smaller (wrong) space
  void add_msg(uint32_t m) {
    uint32_t *pos = std::lower_bound(msgs, msgs + n_msgs, m);
    if (pos != msgs + n_msgs && *pos == m) return;
    if (n_msgs >= MAXM) {
      std::fprintf(stderr, "cpubase: message-set width exceeded MAXM=%d\n",
                   MAXM);
      std::abort();
    }
    std::memmove(pos + 1, pos, (msgs + n_msgs - pos) * sizeof(uint32_t));
    *pos = m;
    n_msgs++;
  }
};

State init_state(const Cfg &cfg) {  // Init — Raft.tla:93-105
  State st;
  std::memset(&st, 0, sizeof(State));
  for (int s = 0; s < cfg.S; s++) {
    st.role[s] = FOLLOWER;
    st.log_len[s] = 1;  // the (0,0) sentinel, Raft.tla:97
    st.commit_index[s] = 1;
    for (int t = 0; t < cfg.S; t++) {
      st.match_index[s][t] = 1;
      st.next_index[s][t] = 2;
    }
  }
  return st;
}

// ---- canonical fingerprint under SYMMETRY + VIEW ----------------------

uint64_t mix64(uint64_t x) {  // splitmix64 finalizer
  x += 0x9e3779b97f4a7c15ull;
  x = (x ^ (x >> 30)) * 0xbf58476d1ce4e5b9ull;
  x = (x ^ (x >> 27)) * 0x94d049bb133111ebull;
  return x ^ (x >> 31);
}

struct Perms {
  int P = 1;
  uint8_t p[5040][MAXS];    // images, 1-based: server s -> p[s-1]
  uint8_t inv[5040][MAXS];  // preimages: slot i holds server inv[i]
  void build(int S, bool stab1) {
    uint8_t idx[MAXS];
    for (int i = 0; i < S; i++) idx[i] = i + 1;
    P = 0;
    do {
      if (stab1 && idx[0] != 1) continue;  // control: fix s1 only
      for (int i = 0; i < S; i++) p[P][i] = idx[i];
      for (int i = 0; i < S; i++) inv[P][idx[i] - 1] = i + 1;
      P++;
    } while (std::next_permutation(idx, idx + S));
  }
};

// Hash of the permuted VIEW (Raft.tla:38 field order; messages as an
// order-independent set-sum so permutation needs no re-sort).
uint64_t view_hash(const Cfg &cfg, const State &st, const uint8_t *p,
                   const uint8_t *inv) {
  uint64_t h = 0x243f6a8885a308d3ull;
  auto pv = [&](uint8_t x) -> uint8_t { return x ? p[x - 1] : 0; };
  for (int i = 0; i < cfg.S; i++) {
    int j = inv[i] - 1;  // original slot feeding permuted slot i
    h = mix64(h ^ pv(st.voted_for[j]));
    h = mix64(h ^ st.current_term[j]);
    uint64_t lh = st.log_len[j];
    for (int k = 0; k < st.log_len[j]; k++)
      lh = mix64(lh ^ (uint64_t(st.log_term[j][k]) << 8 | st.log_val[j][k]));
    h = mix64(h ^ lh);
    for (int t = 0; t < cfg.S; t++)
      h = mix64(h ^ st.match_index[j][inv[t] - 1]);
    for (int t = 0; t < cfg.S; t++)
      h = mix64(h ^ st.next_index[j][inv[t] - 1]);
    h = mix64(h ^ st.commit_index[j]);
    h = mix64(h ^ st.role[j]);
  }
  uint64_t msum = 0;
  for (int i = 0; i < st.n_msgs; i++)
    msum += mix64(0x452821e638d01377ull ^ Msg::permute(st.msgs[i], p));
  return mix64(h ^ msum);
}

uint64_t canon_fp(const Cfg &cfg, const Perms &perms, const State &st) {
  uint64_t best = ~0ull;
  for (int pi = 0; pi < perms.P; pi++) {
    uint64_t h = view_hash(cfg, st, perms.p[pi], perms.inv[pi]);
    if (h < best) best = h;
  }
  if (cfg.fp_bits < 64)  // control: a narrower, salted fingerprint
    best = mix64(best ^ cfg.salt) >> (64 - cfg.fp_bits);
  return best;
}

// Canonical FULL encoding (all 12 vars, permuted, lexicographic min over
// perms): the deterministic representative tiebreak for view-fp
// collisions within a level (aux vars differ -> future enabledness
// differs; cf. oracle/explicit.py "Representative choice").
void full_bytes(const Cfg &cfg, const State &st, const uint8_t *p,
                const uint8_t *inv, std::vector<uint8_t> &out) {
  out.clear();
  auto pv = [&](uint8_t x) -> uint8_t { return x ? p[x - 1] : 0; };
  for (int i = 0; i < cfg.S; i++) {
    int j = inv[i] - 1;
    out.push_back(pv(st.voted_for[j]));
    out.push_back(st.current_term[j]);
    out.push_back(st.role[j]);
    out.push_back(st.log_len[j]);
    for (int k = 0; k < st.log_len[j]; k++) {
      out.push_back(st.log_term[j][k]);
      out.push_back(st.log_val[j][k]);
    }
    for (int t = 0; t < cfg.S; t++) out.push_back(st.match_index[j][inv[t] - 1]);
    for (int t = 0; t < cfg.S; t++) out.push_back(st.next_index[j][inv[t] - 1]);
    out.push_back(st.commit_index[j]);
    uint8_t pend = 0;  // pendingResponse permutes on both axes
    for (int t = 0; t < cfg.S; t++)
      if (st.pending[j] >> (inv[t] - 1) & 1) pend |= 1 << t;
    out.push_back(pend);
  }
  std::vector<uint32_t> pm(st.n_msgs);
  for (int i = 0; i < st.n_msgs; i++) pm[i] = Msg::permute(st.msgs[i], p);
  std::sort(pm.begin(), pm.end());
  for (uint32_t m : pm) {
    out.push_back(m & 0xff); out.push_back(m >> 8 & 0xff);
    out.push_back(m >> 16 & 0xff); out.push_back(m >> 24 & 0xff);
  }
  out.push_back(st.election_count);
  out.push_back(st.restart_count);
  out.push_back(st.val_sent);
}

void canon_full_bytes(const Cfg &cfg, const Perms &perms, const State &st,
                      std::vector<uint8_t> &best) {
  std::vector<uint8_t> cur;
  best.clear();
  for (int pi = 0; pi < perms.P; pi++) {
    full_bytes(cfg, st, perms.p[pi], perms.inv[pi], cur);
    if (best.empty() || cur < best) best.swap(cur);
  }
}

// ---- Inv = LeaderHasAllCommittedEntries (Raft.tla:491-499) ------------
bool inv_ok(const Cfg &cfg, const State &st) {
  bool any_leader = false;
  for (int l = 0; l < cfg.S; l++) {
    if (st.role[l] != LEADER) continue;
    any_leader = true;
    bool bad = false;
    for (int q = 0; q < cfg.S && !bad; q++) {
      if (q == l || st.current_term[q] > st.current_term[l]) continue;
      int cip = st.commit_index[q];
      if (cip > st.log_len[l]) { bad = true; break; }
      for (int i = 0; i < cip; i++)
        if (st.log_term[q][i] != st.log_term[l][i] ||
            st.log_val[q][i] != st.log_val[l][i]) { bad = true; break; }
    }
    if (!bad) return true;  // the spec's exists-quantifier
  }
  return !any_leader;
}

// ---- successor generation (the 11 live Next disjuncts) ----------------

struct Emit {
  std::vector<State> *out;
  uint64_t generated = 0;
  bool split_brain = false;
  void operator()(const State &st) { out->push_back(st); }
};

// BecomeCandidate(s) — Raft.tla:107-130 / explicit.py:119
void become_candidate(const Cfg &cfg, const State &st, int s, Emit &em) {
  if (st.election_count >= cfg.maxE) return;
  if (st.role[s] == LEADER) return;
  State nx = st;
  uint8_t nt = st.current_term[s] + 1;
  nx.election_count++;
  nx.current_term[s] = nt;
  nx.role[s] = CANDIDATE;
  nx.voted_for[s] = s + 1;
  uint8_t lli = st.log_len[s], llt = st.log_term[s][st.log_len[s] - 1];
  for (int p = 0; p < cfg.S; p++)
    if (p != s)
      nx.add_msg(Msg::pack(VOTE_REQ, s + 1, p + 1, nt, lli, llt));
  em.generated++;
  em(nx);
}

// UpdateTerm(s) — Raft.tla:175-188 / explicit.py:146 (branch b carries
// the in-path split-brain Assert, Raft.tla:185)
void update_term(const Cfg &cfg, const State &st, int s, Emit &em) {
  uint8_t cur = st.current_term[s];
  for (int i = 0; i < st.n_msgs; i++) {
    uint32_t m = st.msgs[i];
    if (Msg::dst(m) != s + 1) continue;
    uint8_t term = Msg::term(m);
    if (term > cur) {
      State nx = st;
      nx.role[s] = FOLLOWER;
      nx.current_term[s] = term;
      nx.voted_for[s] = 0;
      em.generated++;
      em(nx);
    }
    if (term == cur && Msg::type(m) == APPEND_REQ) {
      if (st.role[s] == LEADER) { em.split_brain = true; return; }
      if (st.role[s] == CANDIDATE) {
        State nx = st;
        nx.role[s] = FOLLOWER;
        em.generated++;
        em(nx);
      }
    }
  }
}

// ResponseVote(s) — Raft.tla:132-155 / explicit.py:174
void response_vote(const Cfg &cfg, const State &st, int s, Emit &em) {
  if (st.role[s] != FOLLOWER) return;
  uint8_t cur = st.current_term[s];
  uint8_t my_lli = st.log_len[s], my_llt = st.log_term[s][st.log_len[s] - 1];
  for (int i = 0; i < st.n_msgs; i++) {
    uint32_t m = st.msgs[i];
    if (Msg::type(m) != VOTE_REQ || Msg::dst(m) != s + 1 ||
        Msg::term(m) != cur)
      continue;
    uint8_t src = Msg::src(m);
    if (st.voted_for[s] != 0 && st.voted_for[s] != src) continue;
    uint8_t m_lli = Msg::f4(m), m_llt = Msg::f5(m);
    if (!(m_llt > my_llt || (m_llt == my_llt && m_lli >= my_lli))) continue;
    uint32_t grant = Msg::pack(VOTE_RESP, s + 1, src, Msg::term(m));
    if (st.has_msg(grant)) continue;
    State nx = st;
    nx.add_msg(grant);
    nx.voted_for[s] = src;
    em.generated++;
    em(nx);
  }
}

// BecomeLeader(s) — Raft.tla:157-173 / explicit.py:204
void become_leader(const Cfg &cfg, const State &st, int s, Emit &em) {
  if (st.role[s] != CANDIDATE) return;
  uint8_t cur = st.current_term[s];
  int resps = 0;
  for (int i = 0; i < st.n_msgs; i++) {
    uint32_t m = st.msgs[i];
    if (Msg::type(m) == VOTE_RESP && Msg::dst(m) == s + 1 &&
        Msg::term(m) == cur)
      resps++;
  }
  if (resps + 1 < cfg.majority()) return;  // self-vote, Raft.tla:164
  State nx = st;
  nx.role[s] = LEADER;
  for (int u = 0; u < cfg.S; u++) {
    nx.match_index[s][u] = (u == s) ? st.log_len[s] : 1;
    nx.next_index[s][u] = st.log_len[s] + 1;
  }
  nx.pending[s] = 0;
  em.generated++;
  em(nx);
}

// ClientReq(s) — Raft.tla:233-240 / explicit.py:230
void client_req(const Cfg &cfg, const State &st, int s, Emit &em) {
  if (st.role[s] != LEADER) return;
  for (int v = 0; v < cfg.V; v++) {
    if (st.val_sent >> v & 1) continue;
    State nx = st;
    nx.val_sent |= 1 << v;  // := FALSE
    nx.log_term[s][st.log_len[s]] = st.current_term[s];
    nx.log_val[s][st.log_len[s]] = v + 1;
    nx.log_len[s]++;
    nx.match_index[s][s] = st.log_len[s] + 1;
    em.generated++;
    em(nx);
  }
}

// LeaderAppendEntry(s) — Raft.tla:242-269 / explicit.py:249
void leader_append_entry(const Cfg &cfg, const State &st, int s, Emit &em) {
  if (st.role[s] != LEADER) return;
  for (int dst = 0; dst < cfg.S; dst++) {
    if (dst == s) continue;
    uint8_t ni = st.next_index[s][dst];
    if (ni > st.log_len[s] + 1) continue;
    if (st.pending[s] >> dst & 1) continue;
    uint8_t pli = ni - 1, plt = st.log_term[s][pli - 1];
    bool has_e = ni <= st.log_len[s];
    uint32_t m = Msg::pack(APPEND_REQ, s + 1, dst + 1, st.current_term[s],
                           pli, plt, has_e,
                           has_e ? st.log_term[s][ni - 1] : 0,
                           has_e ? st.log_val[s][ni - 1] : 0,
                           st.commit_index[s]);
    if (st.has_msg(m)) continue;
    State nx = st;
    nx.pending[s] |= 1 << dst;
    nx.add_msg(m);
    em.generated++;
    em(nx);
  }
}

// FollowerAcceptEntry(s) — Raft.tla:275-300 / explicit.py:292 (no
// \notin-msgs guard on the accept response)
void follower_accept_entry(const Cfg &cfg, const State &st, int s, Emit &em) {
  if (st.role[s] != FOLLOWER) return;
  uint8_t cur = st.current_term[s];
  for (int i = 0; i < st.n_msgs; i++) {
    uint32_t m = st.msgs[i];
    if (Msg::type(m) != APPEND_REQ || Msg::dst(m) != s + 1 ||
        Msg::term(m) != cur)
      continue;
    uint8_t pli = Msg::f4(m), plt = Msg::f5(m);
    if (!(pli <= st.log_len[s] && st.log_term[s][pli - 1] == plt)) continue;
    bool has_e = Msg::has_e(m);
    int n_ent = has_e ? 1 : 0;
    // new_log = log[:pli] + entries (explicit.py:305)
    uint8_t nl_len = pli + n_ent;
    uint8_t nt[MAXL], nv[MAXL];
    for (int k = 0; k < pli; k++) { nt[k] = st.log_term[s][k]; nv[k] = st.log_val[s][k]; }
    if (has_e) { nt[pli] = Msg::eterm(m); nv[pli] = Msg::eval(m); }
    bool append_new = nl_len > st.log_len[s];
    bool truncated = false;
    if (!append_new) {  // prefix comparison, explicit.py:307
      for (int k = 0; k < nl_len; k++)
        if (nt[k] != st.log_term[s][k] || nv[k] != st.log_val[s][k]) {
          truncated = true;
          break;
        }
    }
    uint8_t lc = Msg::lc(m);
    uint8_t new_commit = std::max(st.commit_index[s],
                                  std::min(lc, nl_len));
    State nx = st;
    nx.add_msg(Msg::pack(APPEND_RESP, s + 1, Msg::src(m), Msg::term(m),
                         pli + n_ent, 1));
    nx.commit_index[s] = new_commit;
    if (truncated || append_new) {
      nx.log_len[s] = nl_len;
      for (int k = 0; k < MAXL; k++) {
        nx.log_term[s][k] = k < nl_len ? nt[k] : 0;
        nx.log_val[s][k] = k < nl_len ? nv[k] : 0;
      }
    }
    em.generated++;
    em(nx);
  }
}

// FollowerRejectEntry(s) — Raft.tla:302-321 / explicit.py:320
void follower_reject_entry(const Cfg &cfg, const State &st, int s, Emit &em) {
  if (st.role[s] != FOLLOWER) return;
  uint8_t cur = st.current_term[s];
  for (int i = 0; i < st.n_msgs; i++) {
    uint32_t m = st.msgs[i];
    if (Msg::type(m) != APPEND_REQ || Msg::dst(m) != s + 1 ||
        Msg::term(m) != cur)
      continue;
    uint8_t pli = Msg::f4(m), plt = Msg::f5(m);
    if (pli <= st.log_len[s] && st.log_term[s][pli - 1] == plt) continue;
    uint32_t rej =
        Msg::pack(APPEND_RESP, s + 1, Msg::src(m), Msg::term(m), pli, 0);
    if (st.has_msg(rej)) continue;
    State nx = st;
    nx.add_msg(rej);
    em.generated++;
    em(nx);
  }
}

// HandleAppendResp(s) — Raft.tla:374-396 / explicit.py:337
void handle_append_resp(const Cfg &cfg, const State &st, int s, Emit &em) {
  if (st.role[s] != LEADER) return;
  uint8_t cur = st.current_term[s];
  for (int i = 0; i < st.n_msgs; i++) {
    uint32_t m = st.msgs[i];
    if (Msg::type(m) != APPEND_RESP || Msg::dst(m) != s + 1 ||
        Msg::term(m) != cur)
      continue;
    uint8_t src = Msg::src(m) - 1, pli = Msg::f4(m);
    bool succ = Msg::f5(m);
    if (!(st.pending[s] >> src & 1)) continue;
    if (succ) {
      if (!(st.match_index[s][src] < pli)) continue;  // Raft.tla:383
      State nx = st;
      nx.match_index[s][src] = pli;
      nx.next_index[s][src] = pli + 1;
      nx.pending[s] &= ~(1 << src);
      em.generated++;
      em(nx);
    } else {
      if (pli + 1 != st.next_index[s][src]) continue;  // Raft.tla:391
      if (!(pli > st.match_index[s][src])) continue;   // Raft.tla:392
      State nx = st;
      nx.pending[s] &= ~(1 << src);
      nx.next_index[s][src] = pli;
      em.generated++;
      em(nx);
    }
  }
}

// LeaderCanCommit(s) — Raft.tla:398-407 / explicit.py:380
void leader_can_commit(const Cfg &cfg, const State &st, int s, Emit &em) {
  if (st.role[s] != LEADER) return;
  uint8_t row[MAXS];
  for (int t = 0; t < cfg.S; t++) row[t] = st.match_index[s][t];
  std::sort(row, row + cfg.S);
  uint8_t median = row[cfg.majority() - 1];  // MajoritySize-th smallest
  if (median <= st.commit_index[s]) return;
  State nx = st;
  nx.commit_index[s] = median;
  em.generated++;
  em(nx);
}

// Restart(s) — Raft.tla:409-414 / explicit.py:394 (leader-only step-down)
void restart(const Cfg &cfg, const State &st, int s, Emit &em) {
  if (st.role[s] != LEADER) return;
  if (st.restart_count >= cfg.maxR) return;
  State nx = st;
  nx.restart_count++;
  nx.role[s] = FOLLOWER;
  em.generated++;
  em(nx);
}

void successors(const Cfg &cfg, const State &st, Emit &em) {
  for (int s = 0; s < cfg.S && !em.split_brain; s++) {
    become_candidate(cfg, st, s, em);
    update_term(cfg, st, s, em);
    response_vote(cfg, st, s, em);
    become_leader(cfg, st, s, em);
    client_req(cfg, st, s, em);
    leader_append_entry(cfg, st, s, em);
    follower_accept_entry(cfg, st, s, em);
    follower_reject_entry(cfg, st, s, em);
    handle_append_resp(cfg, st, s, em);
    leader_can_commit(cfg, st, s, em);
    restart(cfg, st, s, em);
  }
}

// ---- visited set: open-addressing u64 table, read-only during a level -
struct FpSet {
  std::vector<uint64_t> tab;  // 0 = empty (fp 0 is remapped to 1)
  size_t mask = 0, n = 0;
  void init(size_t cap) {
    size_t c = 64;
    while (c < cap * 2) c <<= 1;
    tab.assign(c, 0);
    mask = c - 1;
    n = 0;
  }
  bool contains(uint64_t fp) const {
    if (!fp) fp = 1;
    for (size_t i = fp & mask;; i = (i + 1) & mask) {
      if (tab[i] == fp) return true;
      if (!tab[i]) return false;
    }
  }
  void insert(uint64_t fp) {  // caller guarantees capacity + absence
    if (!fp) fp = 1;
    for (size_t i = fp & mask;; i = (i + 1) & mask) {
      if (tab[i] == fp) return;
      if (!tab[i]) { tab[i] = fp; n++; return; }
    }
  }
  void maybe_grow(size_t incoming) {
    if ((n + incoming) * 2 < tab.size()) return;
    std::vector<uint64_t> old;
    old.swap(tab);
    size_t c = old.size();
    while (c < (n + incoming) * 2) c <<= 1;
    tab.assign(c, 0);
    mask = c - 1;
    size_t keep = n;
    n = 0;
    for (uint64_t fp : old)
      if (fp) insert(fp);
    (void)keep;
  }
};

struct Cand {
  uint64_t fp;
  uint32_t tid;   // producing thread
  uint32_t idx;   // index into that thread's state buffer
};

int run(const Cfg &cfg, int max_depth, int n_threads) {
  Perms perms;
  perms.build(cfg.S, cfg.fold_stab1);
  auto t0 = std::chrono::steady_clock::now();

  State init = init_state(cfg);
  FpSet visited;
  visited.init(1 << 20);
  visited.insert(canon_fp(cfg, perms, init));
  if (!inv_ok(cfg, init)) {
    std::fprintf(stderr, "Invariant violated at Init\n");
    return 1;
  }
  std::vector<State> frontier{init};
  std::vector<uint64_t> level_sizes{1};
  std::vector<uint64_t> gen_by_level;  // cumulative, after each level
  std::atomic<uint64_t> generated{0};
  std::atomic<bool> split_brain{false}, inv_bad{false};
  uint64_t distinct = 1;
  int depth = 0;

  while (!frontier.empty()) {
    if (max_depth >= 0 && depth >= max_depth) break;
    size_t NF = frontier.size();
    std::vector<std::vector<State>> buf(n_threads);
    std::vector<std::vector<Cand>> cands(n_threads);
    auto worker = [&](int tid) {
      Emit em;
      std::vector<State> succ;
      em.out = &succ;
      uint64_t gen = 0;
      for (size_t i = tid; i < NF; i += n_threads) {
        succ.clear();
        em.generated = 0;
        successors(cfg, frontier[i], em);
        gen += em.generated;
        if (em.split_brain) { split_brain = true; return; }
        for (State &nx : succ) {
          uint64_t fp = canon_fp(cfg, perms, nx);
          if (visited.contains(fp)) continue;
          cands[tid].push_back(
              {fp, uint32_t(tid), uint32_t(buf[tid].size())});
          buf[tid].push_back(nx);
        }
      }
      generated += gen;
    };
    std::vector<std::thread> ts;
    for (int t = 0; t < n_threads; t++) ts.emplace_back(worker, t);
    for (auto &t : ts) t.join();
    if (split_brain) {
      std::fprintf(stderr, "split brain Assert fired (Raft.tla:185)\n");
      return 1;
    }
    gen_by_level.push_back(generated.load());
    // level-wide dedup: sort candidates by fp, group, deterministic
    // min-(canonical-full-encoding) representative per group
    std::vector<Cand> all;
    size_t total = 0;
    for (auto &c : cands) total += c.size();
    all.reserve(total);
    for (auto &c : cands) all.insert(all.end(), c.begin(), c.end());
    std::sort(all.begin(), all.end(), [](const Cand &a, const Cand &b) {
      if (a.fp != b.fp) return a.fp < b.fp;
      if (a.tid != b.tid) return a.tid < b.tid;
      return a.idx < b.idx;
    });
    std::vector<State> next;
    visited.maybe_grow(all.size());
    size_t i = 0;
    std::vector<uint8_t> best_bytes, cur_bytes;
    while (i < all.size()) {
      size_t j = i + 1;
      while (j < all.size() && all[j].fp == all[i].fp) j++;
      size_t pick = i;
      if (j - i > 1) {
        canon_full_bytes(cfg, perms, buf[all[i].tid][all[i].idx],
                         best_bytes);
        for (size_t k = i + 1; k < j; k++) {
          canon_full_bytes(cfg, perms, buf[all[k].tid][all[k].idx],
                           cur_bytes);
          if (cur_bytes < best_bytes) {
            best_bytes.swap(cur_bytes);
            pick = k;
          }
        }
      }
      const State &rep = buf[all[pick].tid][all[pick].idx];
      visited.insert(all[i].fp);
      if (!inv_ok(cfg, rep)) inv_bad = true;
      next.push_back(rep);
      i = j;
    }
    if (next.empty()) break;
    distinct += next.size();
    level_sizes.push_back(next.size());
    depth++;
    frontier.swap(next);
    {
      // per-level progress so a crashed/killed deep run still leaves a
      // usable record on stderr (the JSON only prints at the end)
      double el = std::chrono::duration<double>(
                      std::chrono::steady_clock::now() - t0).count();
      std::fprintf(stderr,
                   "[raftref] level %d: new %llu, distinct %llu, "
                   "generated %llu, %.0fs\n",
                   depth, (unsigned long long)level_sizes.back(),
                   (unsigned long long)distinct,
                   (unsigned long long)generated.load(), el);
      std::fflush(stderr);
    }
    if (inv_bad) break;  // the level is interned; report it
  }

  auto t1 = std::chrono::steady_clock::now();
  double secs = std::chrono::duration<double>(t1 - t0).count();
  std::printf("{\"impl\": \"raftref\", \"threads\": %d, "
              "\"S\": %d, \"V\": %d, \"max_election\": %d, "
              "\"max_restart\": %d, \"fp_bits\": %d, \"fold\": \"%s\", "
              "\"ok\": %s, \"distinct\": %llu, "
              "\"generated\": %llu, \"depth\": %d, \"seconds\": %.3f, "
              "\"level_sizes\": [",
              n_threads, cfg.S, cfg.V, cfg.maxE, cfg.maxR, cfg.fp_bits,
              cfg.fold_stab1 ? "stab1" : "full", inv_bad ? "false" : "true",
              (unsigned long long)distinct,
              (unsigned long long)generated.load(), depth, secs);
  for (size_t i = 0; i < level_sizes.size(); i++)
    std::printf("%s%llu", i ? ", " : "", (unsigned long long)level_sizes[i]);
  std::printf("], \"generated_by_level\": [");
  for (size_t i = 0; i < gen_by_level.size(); i++)
    std::printf("%s%llu", i ? ", " : "", (unsigned long long)gen_by_level[i]);
  std::printf("]}\n");
  return 0;
}

}  // namespace

int main(int argc, char **argv) {
  Cfg cfg;
  int max_depth = -1, n_threads = int(std::thread::hardware_concurrency());
  if (argc > 1) cfg.S = std::atoi(argv[1]);
  if (argc > 2) cfg.V = std::atoi(argv[2]);
  if (argc > 3) cfg.maxE = std::atoi(argv[3]);
  if (argc > 4) cfg.maxR = std::atoi(argv[4]);
  if (argc > 5) max_depth = std::atoi(argv[5]);
  if (argc > 6) n_threads = std::atoi(argv[6]);
  for (int a = 7; a + 1 < argc; a += 2) {
    std::string k = argv[a], v = argv[a + 1];
    if (k == "--fp-bits") cfg.fp_bits = std::atoi(v.c_str());
    else if (k == "--salt") cfg.salt = std::strtoull(v.c_str(), nullptr, 10);
    else if (k == "--fold") cfg.fold_stab1 = (v == "stab1");
    else { std::fprintf(stderr, "unknown option %s\n", k.c_str()); return 2; }
  }
  if (cfg.fp_bits < 8 || cfg.fp_bits > 64) {
    std::fprintf(stderr, "--fp-bits must be 8..64\n");
    return 2;
  }
  if (n_threads < 1) n_threads = 1;  // hardware_concurrency() may be 0
  // compile-time caps: MAXS servers, MAXL log entries, and the packed
  // message fields (term/index fields are 4 bits, vals 3)
  if (cfg.S > MAXS || cfg.V + 1 > MAXL || cfg.maxE > 15 || cfg.V > 7) {
    std::fprintf(stderr, "bounds exceed compile-time caps\n");
    return 2;
  }
  return run(cfg, max_depth, n_threads);
}
