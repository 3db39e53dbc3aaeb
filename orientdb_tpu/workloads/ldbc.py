"""LDBC SNB Interactive *short reads* IS1–IS7 as MATCH statements.

The seven short reads are the north-star read workload (BASELINE.json
configs[2] and [4]; SURVEY.md §6 rows 3/5). Each is translated from its
SNB specification to this engine's MATCH dialect:

- IS1  person profile + city          — 1-hop ``isLocatedIn``
- IS2  person's last 10 messages      — ``<-hasCreator-`` then a
       variable-depth ``replyOf`` walk to the root Post (the walk's
       target carries ``class:Post``: traversal passes through Comments
       and emits only the root), then the root's author
- IS3  person's friends               — undirected 1-hop ``knows`` with
       the friendship edge bound (``{as:k}``) for its creationDate
- IS4  message content/date           — single-node MATCH on Message
- IS5  message author                 — 1-hop ``hasCreator``
- IS6  forum + moderator of a message — ``replyOf``-walk to the root
       Post, then ``<-containerOf-`` and ``-hasModerator->``
- IS7  replies to a message + their authors + whether each reply author
       knows the original author — the knows flag is an OPTIONAL cyclic
       arm between two already-bound aliases (a semi-join probe).

Every query is a single MATCH so the whole workload runs on the compiled
TPU path; parameters use the ``:name`` form so plans cache across
parameter values. Parity oracle/TPU is asserted in
``tests/test_ldbc_is.py``.
"""

from __future__ import annotations

from typing import Dict

# IS1: profile of a person, plus the city they live in.
IS1 = (
    "MATCH {class:Person, as:p, where:(id = :personId)}"
    "-isLocatedIn->{as:c} "
    "RETURN p.firstName AS firstName, p.lastName AS lastName, "
    "p.birthday AS birthday, p.locationIP AS locationIP, "
    "p.browserUsed AS browserUsed, c.name AS cityName, "
    "p.creationDate AS creationDate"
)

# IS2: the person's 10 most recent messages; for each, the root post of
# its thread and that post's author. A Post is its own root (the
# var-depth arm emits the origin at depth 0 when it passes the class
# mask), so one row per message.
IS2 = (
    "MATCH {class:Person, as:p, where:(id = :personId)}"
    "<-hasCreator-{as:m}"
    "-replyOf->{as:post, class:Post, while:(true)}, "
    "{as:post}-hasCreator->{as:op} "
    "RETURN m.id AS messageId, m.content AS messageContent, "
    "m.creationDate AS messageCreationDate, post.id AS originalPostId, "
    "op.id AS originalPostAuthorId, "
    "op.firstName AS originalPostAuthorFirstName, "
    "op.lastName AS originalPostAuthorLastName "
    "ORDER BY messageCreationDate DESC, messageId DESC LIMIT 10"
)

# IS3: all friends, most recent friendship first. `knows` is stored as
# one directed edge per pair and queried undirected, per SNB convention.
IS3 = (
    "MATCH {class:Person, as:p, where:(id = :personId)}"
    "-knows{as:k}-{as:f} "
    "RETURN f.id AS personId, f.firstName AS firstName, "
    "f.lastName AS lastName, k.creationDate AS friendshipCreationDate "
    "ORDER BY friendshipCreationDate DESC, personId ASC"
)

# IS4: content + creation date of a message (Post or Comment — Message
# is the abstract superclass, matched polymorphically).
IS4 = (
    "MATCH {class:Message, as:m, where:(id = :messageId)} "
    "RETURN m.creationDate AS messageCreationDate, m.content AS content"
)

# IS5: the author of a message.
IS5 = (
    "MATCH {class:Message, as:m, where:(id = :messageId)}"
    "-hasCreator->{as:p} "
    "RETURN p.id AS personId, p.firstName AS firstName, "
    "p.lastName AS lastName"
)

# IS6: the forum containing a message's thread, and its moderator.
IS6 = (
    "MATCH {class:Message, as:m, where:(id = :messageId)}"
    "-replyOf->{as:post, class:Post, while:(true)}, "
    "{as:post}<-containerOf-{as:f}-hasModerator->{as:mod} "
    "RETURN f.id AS forumId, f.title AS forumTitle, "
    "mod.id AS moderatorId, mod.firstName AS moderatorFirstName, "
    "mod.lastName AS moderatorLastName"
)

# IS7: direct replies to a message, each reply's author, and whether the
# reply author knows the original message's author. The knows probe is an
# OPTIONAL undirected arm between the two bound person aliases: when the
# edge exists the arm binds it ({as:kn}), otherwise the row survives with
# kn = null — so `kn IS NOT NULL` is the boolean the SNB spec asks for.
IS7 = (
    "MATCH {class:Message, as:m, where:(id = :messageId)}"
    "<-replyOf-{as:c}-hasCreator->{as:ra}, "
    "{as:m}-hasCreator->{as:ma}, "
    "{as:ma}-knows{as:kn, optional:true}-{as:ra} "
    "RETURN c.id AS commentId, c.content AS commentContent, "
    "c.creationDate AS commentCreationDate, ra.id AS replyAuthorId, "
    "ra.firstName AS replyAuthorFirstName, "
    "ra.lastName AS replyAuthorLastName, "
    "kn IS NOT NULL AS replyAuthorKnowsOriginalMessageAuthor "
    "ORDER BY commentCreationDate DESC, replyAuthorId ASC"
)

IS_QUERIES: Dict[str, str] = {
    "IS1": IS1,
    "IS2": IS2,
    "IS3": IS3,
    "IS4": IS4,
    "IS5": IS5,
    "IS6": IS6,
    "IS7": IS7,
}


def is_query(name: str) -> str:
    return IS_QUERIES[name.upper()]


# ---------------------------------------------------------------------------
# Interactive COMPLEX reads (IC) — the multi-hop half of the SNB
# interactive workload (BASELINE configs[4]'s "multi-pattern MATCH"
# shape). Translated to this dialect for the entities the offline
# generator covers; each stays a single MATCH so the whole workload
# rides the compiled path.
# ---------------------------------------------------------------------------

# IC1 (transitive friends by name): friends within 3 knows-hops whose
# first name matches, nearest first. The var-depth arm emits each
# reachable person once at its minimum depth.
IC1 = (
    "MATCH {class:Person, as:p, where:(id = :personId)}"
    "-knows-{as:f, while:($depth < 3), "
    "where:(firstName = :firstName AND id <> :personId), "
    "depthAlias: dist} "
    "RETURN f.id AS friendId, f.lastName AS friendLastName, "
    "dist AS distanceFromPerson "
    "ORDER BY distanceFromPerson ASC, friendLastName ASC, friendId ASC "
    "LIMIT 20"
)

# IC2 (recent messages of friends): a friend's messages before a date,
# newest first.
IC2 = (
    "MATCH {class:Person, as:p, where:(id = :personId)}"
    "-knows-{as:f}"
    "<-hasCreator-{as:m, where:(creationDate < :maxDate)} "
    "RETURN f.id AS personId, f.firstName AS personFirstName, "
    "f.lastName AS personLastName, m.id AS messageId, "
    "m.content AS messageContent, m.creationDate AS messageCreationDate "
    "ORDER BY messageCreationDate DESC, messageId ASC LIMIT 20"
)

# IC-shaped aggregate: message volume over the friend-of-friend hull —
# the 3-hop join whose binding table the reference's per-record DFS
# walks row by row, collapsed here into COUNT pushdown weight passes.
ICA = (
    "MATCH {class:Person, as:p, where:(id = :personId)}"
    "-knows-{as:f}"
    "-knows-{as:ff, where:(id <> :personId)}"
    "<-hasCreator-{as:m} "
    "RETURN count(*) AS messageCount"
)

IC_QUERIES: Dict[str, str] = {
    "IC1": IC1,
    "IC2": IC2,
    "ICA": ICA,
}


def ic_query(name: str) -> str:
    return IC_QUERIES[name.upper()]
